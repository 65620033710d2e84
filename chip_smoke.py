#!/usr/bin/env python3
"""Bring-up check: the batch path engine serving on one TPU chip.

Builds a seeded community graph (2^22 vertices, average degree 16, about
67M edges), serves the ``batch_1b`` query shape (512 queries at k=6, a
paths / count / exists mix; ``repro.configs._shapes``) through
``StreamingServer`` with its default planner (BatchEnum: clustering,
shared-query detection, capacity planning) for two rounds of the same
queries, the second one warm, and checks a seeded sample of the answers
exactly against ``repro.core.oracle``.

    python chip_smoke.py            # one chip, the whole check
    python chip_smoke.py --chips 4  # only the sharded path: 4 replicas
                                    # vs one device, compared bit for bit

It exits non-zero, without a result line, when JAX finds no TPU, when the
package sources are missing next to it, or when any phase fails. The last
line of a passing run is ``{"ok": true, "device": {...}}``. Walls printed
here are a bring-up reading, not a benchmark. The persistent compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache`` beside this file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
LOG_N = 22            # 2^22 vertices
AVG_DEG = 16
N_QUERIES = 512       # batch_1b
K = 6
N_COMM = 64           # 65,536-vertex communities
N_ORACLE = 8
SEED = 0
T0 = time.perf_counter()


def say(msg: str) -> None:
    """Print with the seconds since start, so a cut run shows its phase."""
    print(f"[{time.perf_counter() - T0:8.1f}s] {msg}", flush=True)


def _device_bytes(dev) -> tuple[int, int]:
    stats = dev.memory_stats()
    return int(stats["bytes_in_use"]), int(stats["peak_bytes_in_use"])


def make_queries(g, n_queries: int, k: int, seed: int):
    """``n_queries`` PathQuery objects at hop budget ``k``: s uniform, t the
    end of a random walk of 2..k steps from s (so a path of <= k hops
    exists), outputs cycling paths / count / exists."""
    import numpy as np
    from repro.core.query import Output, PathQuery

    rng = np.random.default_rng(seed)
    outputs = (Output.PATHS, Output.COUNT, Output.EXISTS)
    out = []
    while len(out) < n_queries:
        s = v = int(rng.integers(0, g.n))
        for _ in range(int(rng.integers(2, k + 1))):
            nbrs = g.neighbors(v)
            if nbrs.size == 0:
                break
            v = int(nbrs[rng.integers(0, nbrs.size)])
        if v != s:
            out.append(PathQuery(s, v, k, output=outputs[len(out) % 3]))
    return out


def oracle_check(g, queries, results, sample) -> None:
    """Exact comparison of the sampled queries with the DFS oracle."""
    from repro.core.oracle import enumerate_paths_bruteforce, path_set
    from repro.core.query import Output

    for qi in sample:
        q = queries[qi]
        truth = path_set(enumerate_paths_bruteforce(g, q.s, q.t, q.k))
        r = results[qi]
        if q.output is Output.PATHS:
            ok = path_set(r.paths) == truth
        elif q.output is Output.COUNT:
            ok = r.count == len(truth)
        else:
            ok = r.exists == bool(truth)
        if not ok:
            raise AssertionError(f"query {qi} {q.key} {q.output.value}: "
                                 f"engine disagrees with the oracle "
                                 f"({len(truth)} true paths)")


def _say_spans(tr) -> None:
    """The round's heaviest stage spans (host clock), then a fresh buffer."""
    from repro.obs.trace import summarize

    rows = summarize(tr.to_chrome())[:12]
    say("  spans: " + ", ".join(
        f"{r['name']} {r['count']}x {r['total_ms'] / 1e3:.3f}s"
        for r in rows))
    tr.reset()


def serve_rounds(engine, queries, rounds: int):
    """Serve ``queries`` ``rounds`` times through a default
    ``StreamingServer``; returns per-round (results, compile count)."""
    from repro.core import compilelog
    from repro.launch.serve import AdmissionPolicy, StreamingServer
    from repro.obs import trace

    srv = StreamingServer(engine, n_groups=1,
                          policy=AdmissionPolicy(max_batch=len(queries),
                                                 max_delay_s=0.0))
    log = compilelog.enable()
    tr = trace.enable().reset()
    per_round = []
    for rnd in range(rounds):
        snap = log.snapshot()
        t0 = time.perf_counter()
        qids = [srv.submit(q) for q in queries]
        srv.drain()
        results = [srv.results[qid] for qid in qids]
        n_paths = sum(r.count for r, q in zip(results, queries)
                      if q.output.value != "exists")
        wall = time.perf_counter() - t0
        new = log.since(snap)
        n_comp = sum(new.values())
        say(f"round {rnd}: {len(queries)} queries, {n_paths} paths "
            f"counted, {n_comp} compiles, wall {wall!r} s "
            f"(bring-up reading, not a benchmark)")
        for b in srv.batch_log[-1:]:
            say(f"  batch: {b['n_clusters']} clusters, assemble "
                f"{b['t_assemble_s']!r} s, psi={b['n_psi_nodes']} "
                f"materialized={b['n_materialized']} "
                f"hits={b['n_cache_hits']}")
        _say_spans(tr)
        if new:
            top = sorted(new.items(), key=lambda kv: -kv[1])[:10]
            say("  compiles by kernel: " + ", ".join(
                f"{k}={v}" for k, v in top))
        per_round.append((results, n_comp))
    trace.disable()
    return per_round


def run_one_chip(jax) -> None:
    from repro.core import generators
    from repro.core.engine import BatchPathEngine
    import numpy as np

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    g = generators.community(1 << LOG_N, n_comm=N_COMM, avg_deg=AVG_DEG,
                             seed=SEED)
    queries = make_queries(g, N_QUERIES, K, SEED + 1)
    # the oracle sample is drawn before any result exists
    sample = sorted(np.random.default_rng(SEED + 2).choice(
        len(queries), size=N_ORACLE, replace=False).tolist())
    say(f"graph: n={g.n} m={g.m} (community, {N_COMM} communities, "
        f"avg degree {AVG_DEG}), {len(queries)} queries at k={K}, "
        f"host set-up {time.perf_counter() - t0!r} s")
    engine = BatchPathEngine(g)
    backend = engine.kernel_backend.value
    say(f"kernel backend: {backend}")
    if backend != "pallas":
        raise AssertionError(f"resolved kernel backend {backend!r} on TPU")
    jax.block_until_ready(engine.dg.ell_idx)
    in_use, _ = _device_bytes(dev)
    say(f"device bytes after graph upload: {in_use}")

    rounds = serve_rounds(engine, queries, 2)
    in_use, peak = _device_bytes(dev)
    say(f"device bytes: in use {in_use}, peak {peak} "
        f"(graph + index + enumeration)")
    if rounds[-1][1] != 0:
        raise AssertionError(f"warm round compiled {rounds[-1][1]} programs")
    for results, _ in rounds:
        oracle_check(g, queries, results, sample)
    say(f"oracle: {len(sample)} sampled queries {sample} exact in both "
        f"rounds")


def _host(results, queries):
    """Host copy of every answer, for a bit-for-bit comparison."""
    from repro.core.query import Output

    out = []
    for r, q in zip(results, queries):
        if q.output is Output.PATHS:
            out.append(r.paths.copy())
        elif q.output is Output.COUNT:
            out.append(r.count)
        else:
            out.append(r.exists)
    return out


def run_four_chips(jax) -> None:
    """The sharded path alone: one batch on one device, then on a
    4-replica engine (``n_devices=4``), with the same index and the same
    four clusters of 128 queries, one per replica, answers compared bit
    for bit. (Average-linkage clustering of this batch stopped at four
    clusters gives sizes 1, 1, 1 and 509, which would leave three
    replicas idle.)"""
    import gc

    from repro.core import generators
    from repro.core.engine import BatchPathEngine, EngineConfig
    from repro.core.index import build_index
    import numpy as np

    devs = jax.devices()
    if len(devs) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devs)}")
    g = generators.community(1 << LOG_N, n_comm=N_COMM, avg_deg=AVG_DEG,
                             seed=SEED)
    queries = make_queries(g, N_QUERIES, K, SEED + 1)
    say(f"graph: n={g.n} m={g.m}, {len(queries)} queries at k={K}")
    engine = BatchPathEngine(g)
    index = build_index(engine.dg, [q.key for q in queries],
                        backend=engine.kernel_backend.value)
    clusters = [list(range(i, len(queries), 4)) for i in range(4)]
    answers = {}
    for n_dev in (None, 4):
        if n_dev is not None:
            engine = BatchPathEngine(g, EngineConfig(n_devices=n_dev))
        t0 = time.perf_counter()
        report = engine.run(queries, clusters=clusters, index=index)
        answers[n_dev] = _host(report.results, queries)
        wall = time.perf_counter() - t0
        label = "one device" if n_dev is None else "4 replicas"
        say(f"{label}: wall {wall!r} s (bring-up reading, not a "
            f"benchmark)")
        if n_dev is not None:
            for d in report.stats["per_device"]:
                say(f"  {d['device']}: {d['n_clusters']} clusters, "
                    f"{d['n_queries']} queries, wall {d['t_wall_s']!r} s")
            for d in devs[:4]:
                in_use, peak = _device_bytes(d)
                say(f"  {d}: in use {in_use}, peak {peak}")
                if in_use == 0:
                    raise AssertionError(f"{d} holds no state")
        del engine, report
        gc.collect()
    for qi, (a, b) in enumerate(zip(answers[None], answers[4])):
        same = (np.array_equal(a, b) if isinstance(a, np.ndarray)
                else a == b)
        if not same:
            raise AssertionError(f"query {qi}: 4-replica answer differs "
                                 f"from the one-device answer")
    say(f"sharded == one device, bit for bit, on all {len(queries)} "
        f"queries")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no package sources under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(jax)
        count = 4
    else:
        run_one_chip(jax)
        count = len(devs)
    say(f"total wall {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
