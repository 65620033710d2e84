"""One run of one benchmark cell: set up, serve a closed loop for a fixed
window, check every answer against the plain reference, report.

Everything that belongs to a cell is found by name from ``BENCHMARK.json``:
the configuration file it names (``configs``), the traffic mix
``bench/traffic/<traffic>.json`` and one reader per per-layer metric,
``bench/metrics/<metric>.py``. A configuration names its graph generator,
a built-in of ``generators/graphs.py`` or its own ``bench/graphs/<name>.py``.
Adding a configuration, a mix or a metric adds files and entries; this
module does not change.

The served path is the program's ``StreamingServer`` with one replica
group and ``AdmissionPolicy(max_batch=<batch>, max_delay_s=0)`` in front
of a ``BatchPathEngine``. A configuration file may state the settings of
its deployment: the ``EngineConfig`` fields of :data:`SETTINGS`
(``"engine_config"``) and the server's planner (``"planner"``, a
``Planner`` name); without them the engine runs its defaults under
``Planner.BATCH``. The loop is closed: a batch is submitted,
``drain()``-ed and its answers read on the host before the next one is
sent. Set-up serves the mix's warm-up
batches; the window serves a fixed set of fresh batches in turn, whole
batches, lets the batch in flight at ``seconds`` finish and counts it.
Graph and batches are the same for every seed, which orders the queries
inside each batch, so every seed does the same work.
Whatever the window's batches compile that the warm-up did not, they
compile inside the window, and ``qps`` and ``device.compiles_in_window``
show it.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from generators import graphs, queries as qgen
from kernel_bytes import index_bytes
from reference import Adjacency, answer as reference_answer

__all__ = ["Cell", "load_cell", "load_reader", "engine_settings", "run",
           "compare", "WindowContext", "E2E"]

# answers compared with the reference, at most; more are sampled from the
# seed (a 51 s window holds far fewer at the sizes benchmarked)
MAX_CHECKED = 4096
CHECK_LIMITS = {"wrong_paths": 0, "wrong_counts": 0, "wrong_exists": 0,
                "missing": 0}
# the EngineConfig fields a configuration may set, with their JSON type:
# settings a deployment states, not how the program is built or traced
SETTINGS = {"cache_bytes": int}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its
    configuration file, traffic mix and the metrics it reports: every
    cell reports every metric."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{w['traffic']}.json").read_text())
    return Cell(workload, config, mix, int(w["chips"]),
                tuple(bench["end_to_end"]), tuple(bench["per_layer"]))


def load_reader(root: Path, name: str) -> Callable:
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return graphs.load_file(root / "bench" / "metrics" / f"{name}.py",
                            "read")


@dataclasses.dataclass
class WindowContext:
    """What a per-layer reader may read about the measured window.

    ``spans`` are the program's stage spans ``(name, t0, t1)`` (host clock)
    that started inside the window; ``batches`` the server's ``batch_log``
    entries of the window's batches; ``device`` the reduction of the
    profiler trace (``trace_reduce.Reduction``), or None without one.
    """

    window_s: float
    n_answered: int
    spans: list
    batches: list
    compiles_in_window: int
    index_bytes: int
    peaks: dict
    device: Optional[object] = None

    def span_total(self, *names: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.spans if n in names)

    def span_count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.spans if n == name)


def compare(adj: Adjacency, window: list, rng) -> dict:
    """Compare the window's answers with the reference: every one, or a
    seeded sample of ``MAX_CHECKED``. ``window`` holds ``((s, t, k,
    output), answer)``; an answer of None never came. Returns the counts
    of wrong and missing answers by kind, and how many were checked."""
    idx = np.arange(len(window))
    if idx.size > MAX_CHECKED:
        idx = np.sort(rng.choice(idx, size=MAX_CHECKED, replace=False))
    out = dict.fromkeys(CHECK_LIMITS, 0)
    for i in idx:
        (s, t, k, kind), got = window[i]
        if got is None:
            out["missing"] += 1
            continue
        want = reference_answer(adj, s, t, k, kind)
        if kind == "paths":
            got = sorted(tuple(int(x) for x in row if x >= 0)
                         for row in got)
        elif kind == "count":
            got = int(got)
        else:
            got = bool(got)
        if got != want:
            out[{"paths": "wrong_paths", "count": "wrong_counts",
                 "exists": "wrong_exists"}[kind]] += 1
    out["checked"] = int(idx.size)
    return out


# the end-to-end metrics the harness takes itself, by name
E2E = {
    "qps": lambda w: w["n_answered"] / w["window_s"],
    "setup_s": lambda w: w["setup_s"],
}


def engine_settings(config: dict, planner_cls) -> tuple[dict, object]:
    """The ``EngineConfig`` overrides and the ``Planner`` that ``config``
    states under ``"engine_config"`` and ``"planner"``: ``({}, BATCH)``
    without them. An override names a field of :data:`SETTINGS` and holds
    a value of its type; the planner is a member name. Anything else
    raises ``ValueError`` naming the key."""
    overrides = config.get("engine_config", {})
    if not isinstance(overrides, dict):
        raise ValueError("engine_config must be an object of EngineConfig "
                         "fields")
    for key, value in overrides.items():
        if key not in SETTINGS:
            raise ValueError(f"engine_config key {key!r} is not one of the "
                             f"settings a configuration may state: "
                             f"{sorted(SETTINGS)}")
        if type(value) is not SETTINGS[key]:
            raise ValueError(f"engine_config key {key!r}: {value!r} is not "
                             f"a {SETTINGS[key].__name__}")
    name = config.get("planner", "BATCH")
    if name not in planner_cls.__members__:
        raise ValueError(f"planner {name!r} is not one of "
                         f"{list(planner_cls.__members__)}")
    return dict(overrides), planner_cls[name]


def _device_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _serve(srv, batch) -> list:
    """Submit one batch, drain it, read every answer on the host. Returns
    the answers, None for one that never came."""
    from repro.core.query import PathQuery

    qids = [srv.submit(PathQuery(s, t, k, output=kind))
            for s, t, k, kind in batch]
    srv.drain()
    answers = []
    for qid, (_, _, _, kind) in zip(qids, batch):
        try:
            r = srv.take(qid)
        except KeyError:
            r = None
        if r is None or not r.ok:
            got = None
        else:
            got = getattr(r, kind)
        answers.append(got)
    return answers


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, require_tpu: bool = True,
        say: Callable[[str], None] = lambda s: None) -> tuple[dict, dict]:
    """Run the cell once. Returns the result line (without the checks) and
    the checks, each ``{"value": n, "limit": n}``. Raises ``RuntimeError``
    when ``require_tpu`` and JAX finds no TPU or too few chips."""
    cell = load_cell(root, workload)
    src = root / "src"
    if not (src / "repro").is_dir():
        raise RuntimeError(f"no program sources under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import os

    # libtpu logs to a fixed path under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / "bench" / ".jax_cache"))
    # every program goes to the persistent cache, so that only the first
    # run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found platform {dev.platform!r}")
    if len(devs) < cell.chips:
        raise RuntimeError(f"cell {workload} needs {cell.chips} chips, JAX "
                           f"found {len(devs)}")
    from peaks import peaks
    pk = peaks(dev.device_kind) if require_tpu else {}
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={cell.chips}")

    from repro.core import compilelog
    from repro.core.engine import BatchPathEngine, EngineConfig
    from repro.core.graph import Graph
    from repro.core.query import Planner
    from repro.launch.serve import AdmissionPolicy, StreamingServer
    from repro.obs import trace as obstrace

    overrides, planner = engine_settings(cell.config, Planner)
    n, asrc, adst = graphs.make(cell.config, root / "bench" / "graphs")
    # the query stream's own CSR: the walks that draw queries read it
    walks = Adjacency.build(n, asrc, adst)
    g = Graph.from_edges(n, asrc, adst)
    traced = (dict(trace=True, trace_fence=True, trace_annotations=True)
              if trace else {})
    engine = BatchPathEngine(g, EngineConfig(**overrides, **traced))
    jax.block_until_ready((engine.dg.ell_idx, engine.dg.r_ell_idx))
    say(f"graph: n={n} m={walks.m}, ELL {engine.dg.ell_cap}/"
        f"{engine.dg.r_ell_cap}, engine_config {overrides}, planner "
        f"{planner.name}, host set-up "
        f"{time.perf_counter() - t_start:.3f} s")
    mix = cell.mix
    srv = StreamingServer(engine, n_groups=1, policy=AdmissionPolicy(
        max_batch=int(mix["batch"]), max_delay_s=0.0), planner=planner)
    clock = time.perf_counter
    log = compilelog.enable()
    warm = qgen.warm_batches(walks, mix)
    for b in warm:
        _serve(srv, b)
    say(f"warm-up: {len(warm)} batches, {log.total} compiles")
    pool = qgen.window_batches(walks, mix, seed)

    trace_dir = root / "bench" / ".traces" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        obstrace.tracer().reset()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no event per Python call
        opts.host_tracer_level = 1        # the program's annotations only
        opts.enable_hlo_proto = False     # programs are read by name
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    n_log0 = len(srv.batch_log)
    snap = log.snapshot()
    window, nbytes, walls = [], 0, []
    t0 = clock()
    setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        i = 0
        while True:
            batch = pool[i % len(pool)]
            tb = clock()
            window += list(zip(batch, _serve(srv, batch)))
            walls.append(clock() - tb)
            nbytes += index_bytes(
                n, walks.m, len({q[0] for q in batch}),
                len({q[1] for q in batch}), max(q[2] for q in batch))
            i += 1
            if clock() - t0 >= seconds:
                break
    t1 = clock()
    new_programs = log.since(snap)
    compiles = sum(new_programs.values())
    if trace:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    n_answered = sum(a is not None for _, a in window)
    peak = max(_device_bytes(d) for d in devs[:cell.chips])
    say(f"window: {i} batches, {len(window)} queries in {window_s:.3f} s, "
        f"batch walls {[round(w, 3) for w in walls]} s, {compiles} compiles "
        f"{new_programs}, peak device bytes {peak}")

    spans = [(sp.name, sp.t0, sp.t1) for sp in obstrace.tracer().spans()
             if t0 <= sp.t0 <= t1] if trace else []
    served = srv.batch_log[n_log0:]
    obstrace.disable()
    del srv, engine, g
    gc.collect()

    state = {"n_answered": n_answered, "window_s": window_s,
             "setup_s": setup_s}
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": E2E[m["name"]](state),
                                  "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    line = {"correct": None, "attempted": len(window),
            "failed": len(window) - n_answered, "metrics": metrics,
            "device": device}
    if trace:
        import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, window_span="bench.window")
        ctx = WindowContext(window_s=window_s, n_answered=n_answered,
                            spans=spans, batches=served,
                            compiles_in_window=compiles,
                            index_bytes=nbytes, peaks=pk, device=red)
        for m in cell.per_layer:
            value = load_reader(root, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            line["breakdown"] = red.breakdown()

    t_ref = clock()
    del walks
    adj = Adjacency.build(n, asrc, adst)
    found = compare(adj, window, qgen.seeded(seed, qgen.SAMPLE))
    say(f"reference: {found['checked']} answers checked in "
        f"{clock() - t_ref:.3f} s")
    checks = {k: {"value": found[k], "limit": lim}
              for k, lim in CHECK_LIMITS.items()}
    line["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    return line, checks
