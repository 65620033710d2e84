"""Kernel micro-benchmarks + dispatch-shape accounting for the fused path.

Three sections, all returned as a dict (and written to
``results/BENCH_kernels.json``) so ``check_regression.py --kernels`` can
gate them:

  * **timing / bandwidth** — the jnp reference twins the engine actually
    executes off-TPU, timed warm (best-of-N), with an analytic per-call
    HBM-traffic model per op. ``achieved_gbps`` is this machine's
    effective bandwidth; ``roofline_frac`` relates it to the TPU-v5e HBM
    roof from ``launch.roofline.HW`` (the deploy target the Pallas path
    is tiled for). Interpret mode is a correctness backend, not a
    performance proxy, so it is never timed here.
  * **dispatch counts** — one MS-BFS level on the packed route (the
    ``msbfs_step`` expand + dedup, then the ``msbfs_count`` distance
    kernel) against the segment-op level of the edge-list sweep. Both
    arms are traced and their jaxpr equations counted (pallas_call
    bodies count as one);
    the jnp arm is additionally compiled and its HLO entry-computation
    op count recorded (``launch.hlo_analysis.count_entry_ops``). These
    are deterministic, hardware-independent integers — gateable in CI.
  * **warm retraces** — the packed sweeps run twice on identical shapes
    under the compile recorder; the second pass must add zero compiles
    (the zero-warm-retrace guarantee must survive the kernel route).
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import count_entry_ops, count_eqns
from repro.launch.roofline import HW

from .common import record


def _bench(fn, *args, repeats=5):
    jax.tree.leaves(fn(*args))[0].block_until_ready()
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree.leaves(out)[0].block_until_ready()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def _op_row(name: str, dt: float, nbytes: float, derived: str = "") -> dict:
    gbps = nbytes / dt / 1e9
    frac = gbps * 1e9 / HW["hbm_bw"]
    record(f"kernel_{name}", dt * 1e6,
           f"{derived}{';' if derived else ''}GBps={gbps:.2f};"
           f"roofline_frac={frac:.4f}")
    return {"us": dt * 1e6, "bytes": nbytes, "achieved_gbps": gbps,
            "roofline_frac": frac}


# eqn accounting lives in launch.hlo_analysis.count_eqns, shared with the
# repro.analysis jaxpr audit so bench numbers and budget gates agree


def _dispatch_counts(n: int, D: int, S: int, seed: int = 0) -> dict:
    """Per-level op footprint of the two MS-BFS arms on identical shapes.

    The jnp arm is one level of :func:`repro.core.msbfs.msbfs_dist`
    (expand + dedup + distance write as separate segment/mask ops); the
    fused arm is the same level on the packed route (interpret mode —
    the dispatch shape is identical to the compiled TPU kernel, only the
    body execution differs). Both jaxpr-eqn counts come from the same
    tracer, so the comparison is apples-to-apples and deterministic.
    """
    from repro.core.msbfs import msbfs_hop
    from repro.kernels.msbfs_expand.ops import msbfs_step, unreached_count

    rng = np.random.default_rng(seed)
    m = n * 4
    esrc = jnp.asarray(rng.integers(0, n, m).astype(np.int32))
    edst = jnp.asarray(np.sort(rng.integers(0, n, m).astype(np.int32)))
    ell = jnp.asarray(rng.integers(0, n + 1, (n + 1, D)).astype(np.int32)
                      ).at[n].set(n)
    W = -(-S // 32)
    frontier8 = jnp.asarray((rng.random((n + 1, S)) < 0.05).astype(np.int8))
    dist8 = jnp.asarray(rng.integers(0, 9, (n + 1, S)).astype(np.int8))
    fr_w = jnp.asarray(rng.integers(0, 2**32, (n + 1, W), dtype=np.uint64)
                       .astype(np.uint32))
    vis_w = fr_w[:n]
    count_w = jnp.asarray(rng.integers(0, 9, (W * 32, n)).astype(np.int8))

    def level_jnp(frontier, dist):
        reached = (dist < jnp.int8(9)).astype(jnp.int8)
        nxt = msbfs_hop(frontier, esrc, edst, n)
        new = nxt * (1 - reached)
        dist = jnp.where(new.astype(bool), jnp.int8(3), dist)
        return new.at[n].set(0), dist

    def level_fused(frontier, visited, count):
        f, v = msbfs_step(ell[:n], frontier, visited, backend="interpret")
        count = unreached_count(v, count, backend="interpret")
        return jnp.concatenate([f, jnp.zeros((1, W), jnp.uint32)]), v, count

    jnp_eqns = count_eqns(jax.make_jaxpr(level_jnp)(frontier8, dist8).jaxpr)
    fused_eqns = count_eqns(
        jax.make_jaxpr(level_fused)(fr_w, vis_w, count_w).jaxpr)
    # compiled footprint of the jnp arm (the fused arm's Pallas kernel
    # cannot lower off-TPU; its dispatch count IS the jaxpr count)
    hlo = jax.jit(level_jnp).lower(frontier8, dist8).compile().as_text()
    return {"n": n, "ell_width": D, "sources": S,
            "jnp_eqns_per_level": jnp_eqns,
            "fused_eqns_per_level": fused_eqns,
            "jnp_hlo_entry_ops": count_entry_ops(hlo)}


def _warm_retraces(n: int, D: int, S: int) -> dict:
    """Run the packed ELL sweeps twice on identical shapes; the second
    pass must hit only warm jit caches (zero new compiles)."""
    from repro.core import compilelog
    from repro.core.msbfs import msbfs_dist_ell, msbfs_set_dist_ell

    rng = np.random.default_rng(1)
    ell = jnp.asarray(rng.integers(0, n + 1, (n + 1, D)).astype(np.int32)
                      ).at[n].set(n)
    srcs = jnp.asarray(rng.choice(n, size=S, replace=False).astype(np.int32))
    seed = np.zeros(n + 1, np.int8)
    seed[np.asarray(srcs)[:4]] = 1
    seed = jnp.asarray(seed)

    def sweep():
        d = msbfs_dist_ell(ell, srcs, n=n, k_max=4, backend="interpret")
        sd = msbfs_set_dist_ell(ell, seed, n=n, k_max=4, backend="interpret")
        jax.block_until_ready((d, sd))

    rec = compilelog.enable()
    sweep()                      # cold: pays the compiles
    snap = rec.snapshot()
    sweep()                      # warm: must add zero
    return {"warm_retraces": rec.compiles_since(snap),
            "warm_compiles_by_kernel": rec.since(snap)}


def main(scale: float = 1.0) -> dict:
    rng = np.random.default_rng(0)
    out: dict = {"ops": {}}

    # packed MS-BFS expand + dedup (msbfs_step, jnp): 200k vertices,
    # deg-8 ELL, 128 packed sources
    from repro.kernels.msbfs_expand.ops import msbfs_step
    n, D, S = max(int(200_000 * scale), 4096), 8, 128
    W = S // 32
    ell = jnp.asarray(rng.integers(0, n + 1, (n + 1, D)).astype(np.int32)
                      ).at[n].set(n)
    fr = jnp.asarray(rng.integers(0, 2**32, (n + 1, W), dtype=np.uint64)
                     .astype(np.uint32))
    vis = fr[:n]
    f = jax.jit(lambda a, b: msbfs_step(ell[:n], a, b, backend="jnp"))
    dt = _bench(f, fr, vis)
    # traffic: ELL rows + gathered frontier words + visited r/w + new out
    nbytes = n * D * 4 + n * D * W * 4 + 2 * n * W * 4 + n * W * 4
    out["ops"]["msbfs_step_jnp"] = _op_row(
        "msbfs_step_jnp", dt, nbytes,
        f"V={n};D={D};S={S};GTEPS={n * D * S / dt / 1e9:.2f}")

    # edge-list MS-BFS hop (segment-op path the jnp engine runs)
    from repro.core.msbfs import msbfs_hop
    m = int(1_600_000 * scale)
    esrc = jnp.asarray(rng.integers(0, n, m).astype(np.int32))
    edst = jnp.asarray(np.sort(rng.integers(0, n, m).astype(np.int32)))
    frontier = jnp.asarray((rng.random((n + 1, S)) < 0.05).astype(np.int8))
    f = jax.jit(lambda fr_: msbfs_hop(fr_, esrc, edst, n))
    dt = _bench(f, frontier)
    nbytes = m * 4 * 2 + m * S + n * S   # edges + gathered rows + segment out
    out["ops"]["msbfs_hop_jnp"] = _op_row(
        "msbfs_hop_jnp", dt, nbytes,
        f"edges={m};sources={S};GTEPS={m * S / dt / 1e9:.2f}")

    # pairwise popcount (similarity): 128 queries x n vertices
    from repro.kernels.pairwise_popcount.ref import intersections_bool_ref
    g = jnp.asarray(rng.random((128, n)) < 0.1)
    f = jax.jit(intersections_bool_ref)
    dt = _bench(f, g)
    out["ops"]["similarity_jnp"] = _op_row(
        "similarity_jnp", dt, 128 * n * 2 + 128 * 128 * 4, f"Q=128;V={n}")

    # row-aligned join validity (kernel twin the engine joins route):
    # 64k candidate pairs, halves of length 6
    from repro.kernels.path_join.ref import rowwise_overlap_ref
    N = 1 << 16
    A = jnp.asarray(rng.integers(0, 1000, (N, 6)).astype(np.int32))
    B = jnp.asarray(rng.integers(0, 1000, (N, 6)).astype(np.int32))
    f = jax.jit(rowwise_overlap_ref)
    dt = _bench(f, A, B)
    out["ops"]["rowwise_overlap_jnp"] = _op_row(
        "rowwise_overlap_jnp", dt, N * 6 * 4 * 2 + N * 4,
        f"rows={N};Mrows_s={N / dt / 1e6:.1f}")

    # dense path-pair overlap (detect-stage kernel): 4096 x 4096, L=6
    from repro.kernels.path_join.ref import path_overlap_ref
    A = jnp.asarray(rng.integers(0, 1000, (4096, 6)).astype(np.int32))
    B = jnp.asarray(rng.integers(0, 1000, (4096, 6)).astype(np.int32))
    f = jax.jit(path_overlap_ref)
    dt = _bench(f, A, B)
    out["ops"]["path_overlap_jnp"] = _op_row(
        "path_overlap_jnp", dt, 2 * 4096 * 6 * 4 + 4096 * 4096 * 4,
        f"pairs={4096 * 4096};Mpairs_s={4096 * 4096 / dt / 1e6:.1f}")

    # chunked attention (flash twin): B4 S2048 H8 hd64
    from repro.models.transformer import chunked_attention
    q = jnp.asarray(rng.standard_normal((4, 2048, 8, 64)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((4, 2048, 2, 64)).astype(np.float32))
    f = jax.jit(lambda a, b, c: chunked_attention(a, b, c, causal=True,
                                                  q_offset=0, chunk=512))
    dt = _bench(f, q, k, k)
    flops = 4 * 4 * 2048 * 2048 * 8 * 64 / 2
    out["ops"]["attention_jnp"] = _op_row(
        "attention_jnp", dt, (4 * 2048 * 8 * 64 * 4) * 4,
        f"gflops={flops / dt / 1e9:.1f}")

    # ---- dispatch-shape accounting (deterministic; CI-gated) ----------
    dn = max(int(50_000 * scale), 2048)
    out["dispatch"] = _dispatch_counts(dn, D, S)
    record("kernel_dispatch_eqns_per_level",
           out["dispatch"]["fused_eqns_per_level"],
           f"jnp={out['dispatch']['jnp_eqns_per_level']};"
           f"jnp_hlo_entry_ops={out['dispatch']['jnp_hlo_entry_ops']}")

    out.update(_warm_retraces(max(int(20_000 * scale), 1024), D, 64))
    record("kernel_warm_retraces", out["warm_retraces"],
           str(out["warm_compiles_by_kernel"]))

    out["hw"] = {"hbm_bw": HW["hbm_bw"], "peak_flops": HW["peak_flops"]}

    dest = Path("results/BENCH_kernels.json")
    dest.parent.mkdir(parents=True, exist_ok=True)
    dest.write_text(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()
