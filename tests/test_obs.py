"""repro.obs: hierarchical span tracer, metrics registry, Chrome-trace
export, engine/serving integration, and the zero-overhead contracts —
tracing off must not change results or warm retraces, and the t_* stats
must stay derived views over spans either way."""
import json
import threading
from collections import Counter

import numpy as np
import pytest

from repro.core import BatchPathEngine, EngineConfig, PathSession
from repro.core.graph import Graph
from repro.core.oracle import path_set
from repro.obs import metrics as obsmetrics
from repro.obs import trace as obstrace

OFFSETS = (1, 2, 3)
# NOT 64: test_recompile.py uses the same circulant harness at n=64 and
# asserts its cold start compiles > 0 — the jit cache is process-global,
# so this suite (alphabetically earlier) must warm different shapes
N = 48


def circulant(n=N, offsets=OFFSETS) -> Graph:
    """Vertex-transitive graph (same harness as test_recompile): any
    compile observed in a warm window is a genuine leak, not workload
    noise."""
    src = np.repeat(np.arange(n, dtype=np.int64), len(offsets))
    dst = (src + np.tile(np.array(offsets, np.int64), n)) % n
    return Graph.from_edges(n, src, dst)


QS = [(0, 3, 3), (8, 11, 3), (16, 19, 3)]


@pytest.fixture(autouse=True)
def _clean_tracer():
    """The tracer is a process singleton — leave it disabled and empty so
    obs tests cannot leak recording into unrelated suites."""
    tr = obstrace.tracer()
    was = tr.enabled
    yield
    tr.enabled = was
    obstrace.disable()
    tr.reset()


# ----------------------------------------------------------------------
# trace.py unit behavior
# ----------------------------------------------------------------------
class TestSpans:
    def test_nesting_depth_and_order(self):
        tr = obstrace.Tracer(enabled=True)
        with tr.span("outer") as so:
            with tr.span("inner", level=1) as si:
                pass
        spans = tr.spans()
        # inner finishes (and records) first; depths reflect the stack
        assert [s.name for s in spans] == ["inner", "outer"]
        assert si.depth == 1 and so.depth == 0
        assert si.tid == so.tid == threading.get_ident()
        assert 0 <= si.duration <= so.duration

    def test_exception_safety_records_and_unwinds(self):
        tr = obstrace.Tracer(enabled=True)
        with pytest.raises(ValueError):
            with tr.span("outer"):
                with tr.span("boom"):
                    raise ValueError("x")
        # both spans recorded, error tagged, stack fully unwound
        by_name = {s.name: s for s in tr.spans()}
        assert by_name["boom"].attrs["error"] == "ValueError"
        assert by_name["outer"].attrs["error"] == "ValueError"
        assert tr._stack() == []
        with tr.span("after") as sp:
            pass
        assert sp.depth == 0

    def test_disabled_tracer_still_times(self):
        tr = obstrace.Tracer(enabled=False)
        with tr.span("stage") as sp:
            sum(range(1000))
        assert sp.duration > 0.0          # t_* stats work untraced
        assert len(tr) == 0               # ...but nothing is recorded

    def test_set_and_elapsed(self):
        tr = obstrace.Tracer(enabled=True)
        with tr.span("s", a=1) as sp:
            assert sp.elapsed >= 0.0
            sp.set(hit=True)
        assert sp.attrs == {"a": 1, "hit": True}

    def test_ring_buffer_bounded(self):
        tr = obstrace.Tracer(enabled=True, capacity=4)
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]

    def test_thread_local_stacks_give_thread_roots(self):
        tr = obstrace.Tracer(enabled=True)
        done = threading.Event()

        def worker():
            with tr.span("worker.root"):
                pass
            done.set()

        with tr.span("main.root"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert done.is_set()
        by_name = {s.name: s for s in tr.spans()}
        # the worker's span is a root on its own thread, not a child
        assert by_name["worker.root"].depth == 0
        assert by_name["worker.root"].tid != by_name["main.root"].tid

    def test_chrome_trace_round_trip(self, tmp_path):
        tr = obstrace.Tracer(enabled=True)
        with tr.span("engine.run", n_queries=3):
            with tr.span("enumerate.level", level=0):
                pass
            with tr.span("join.keyed", lam=2):
                pass
        path = tmp_path / "trace.json"
        doc = tr.export(path)
        loaded = obstrace.load(path)
        assert loaded == json.loads(json.dumps(doc))
        assert loaded["displayTimeUnit"] == "ms"
        assert obstrace.stage_names(loaded) == \
            {"engine.run", "enumerate.level", "join.keyed"}
        ev = {e["name"]: e for e in loaded["traceEvents"]
              if e.get("ph") == "X"}
        assert ev["engine.run"]["args"] == {"n_queries": 3, "depth": 0}
        assert ev["enumerate.level"]["args"]["depth"] == 1
        assert ev["enumerate.level"]["ts"] >= ev["engine.run"]["ts"]
        # metadata thread_name event present
        assert any(e.get("ph") == "M" for e in loaded["traceEvents"])

    def test_summarize_and_coverage(self):
        tr = obstrace.Tracer(enabled=True)
        with tr.span("engine.run"):
            for lv in range(3):
                with tr.span("enumerate.level", level=lv):
                    sum(range(20000))
        doc = tr.to_chrome()
        rows = {r["name"]: r for r in obstrace.summarize(doc)}
        assert rows["enumerate.level"]["count"] == 3
        assert rows["engine.run"]["total_ms"] >= \
            rows["enumerate.level"]["total_ms"] * 0.9
        cov = obstrace.coverage(doc, root="engine.run")
        assert 0.5 <= cov <= 1.0

    def test_singleton_enable_disable(self):
        tr = obstrace.enable()
        assert tr is obstrace.tracer() and tr.enabled
        with obstrace.span("via.module"):
            pass
        assert "via.module" in {s.name for s in tr.spans()}
        obstrace.disable()
        n = len(tr)
        with obstrace.span("dropped"):
            pass
        assert len(tr) == n


# ----------------------------------------------------------------------
# metrics.py unit behavior
# ----------------------------------------------------------------------
class TestMetrics:
    def test_counter_gauge_get_or_create(self):
        reg = obsmetrics.MetricsRegistry()
        c = reg.counter("hits", cache="0")
        c.inc()
        c.inc(2.0)
        assert reg.counter("hits", cache="0") is c and c.value == 3.0
        assert reg.counter("hits", cache="1") is not c
        g = reg.gauge("bytes")
        g.set(10)
        g.dec(4)
        assert g.value == 6.0

    def test_histogram_quantiles_match_numpy(self):
        # bucket width is ~19% relative — interpolated quantiles must land
        # within one bucket of the exact order statistic
        rng = np.random.default_rng(0)
        samples = rng.lognormal(mean=-4.0, sigma=1.5, size=5000)
        h = obsmetrics.Histogram()
        for x in samples:
            h.record(float(x))
        for q in (0.5, 0.95, 0.99):
            exact = float(np.percentile(samples, q * 100))
            got = h.quantile(q)
            assert abs(got - exact) <= 0.25 * exact, (q, got, exact)
        assert h.count == 5000
        assert h.quantile(0.0) >= float(samples.min())
        assert h.quantile(1.0) <= float(samples.max())
        assert abs(h.mean - samples.mean()) < 1e-9 * samples.sum() + 1e-12

    def test_histogram_clamped_to_observed_range(self):
        h = obsmetrics.Histogram()
        h.record(0.010)
        h.record(0.012)
        for q in (0.5, 0.99):
            assert 0.010 <= h.quantile(q) <= 0.012

    def test_since_windows_isolate_samples(self):
        reg = obsmetrics.MetricsRegistry()
        h = reg.histogram("lat_s")
        h.record(1.0)
        snap = reg.snapshot()
        for _ in range(10):
            h.record(0.001)
        win = reg.since(snap)[("lat_s", ())]
        assert win.count == 10
        # the pre-snapshot 1.0s outlier must not contaminate the window
        assert win.quantile(0.99) < 0.01
        assert reg.since(reg.snapshot()) == {}   # empty window -> no entry

    def test_render_exposition(self):
        reg = obsmetrics.MetricsRegistry()
        reg.counter("cache_hits_total", cache="0").inc(5)
        reg.histogram("lat_s").record(0.5)
        text = reg.render()
        assert "# TYPE cache_hits_total counter" in text
        assert 'cache_hits_total{cache="0"} 5' in text
        assert "lat_s_count 1" in text
        assert 'quantile="0.99"' in text


# ----------------------------------------------------------------------
# engine / session integration
# ----------------------------------------------------------------------
def _engine(**cfg) -> BatchPathEngine:
    base = dict(min_cap=256, cache_bytes=8 << 20)
    base.update(cfg)
    return BatchPathEngine(circulant(), EngineConfig(**base))


class TestEngineIntegration:
    def test_traced_run_exports_full_taxonomy(self, tmp_path):
        eng = _engine(trace=True)
        eng.obs.reset()
        r = eng.run(QS)
        assert r.stats["t_wall_s"] > 0
        doc = eng.obs.export(tmp_path / "t.json")
        names = obstrace.stage_names(doc)
        # join.splice is absent here by design: it fires only when a
        # cluster splices shared-prefix children (the exp8 obs benchmark
        # pins the fuller taxonomy on a sharing-heavy workload)
        for stage in ("engine.run", "cluster.queries", "detect.cluster",
                      "cache.get", "index.build", "enumerate.level",
                      "enumerate.node", "enumerate.cluster",
                      "join.keyed", "assemble.query"):
            assert stage in names, stage
        assert obstrace.coverage(doc, root="engine.run") >= 0.9

    def test_stats_are_span_derived_views(self):
        # t_* keys exist traced AND untraced (always-on timing)
        for trace in (False, True):
            r = _engine(trace=trace).run(QS)
            for k in ("t_wall_s", "t_cluster", "t_detect",
                      "t_build_index", "t_enumerate"):
                assert k in r.stats and r.stats[k] >= 0.0, (trace, k)

    def test_tracing_off_is_bit_identical(self):
        r0 = _engine(trace=False).run(QS)
        r1 = _engine(trace=True).run(QS)
        for qi in range(len(QS)):
            assert path_set(r0[qi].paths) == path_set(r1[qi].paths)

    def test_traced_warm_batches_compile_nothing(self):
        # the recompile pin of test_recompile, with tracing ON: spans and
        # metrics must not introduce retraces or host-shape drift
        eng = _engine(trace=True, log_compiles=True)

        def batch(i):
            return [(8 * j + i, (8 * j + i + 3) % N, 3) for j in range(6)]

        eng.run(batch(0))
        for i in (1, 2):
            r = eng.run(batch(i))
            assert r.stats["n_compiles"] == 0, r.stats["compiled_kernels"]
            assert r.stats["n_retraces"] == 0

    def test_cache_metrics_isolated_per_engine_via_since(self):
        reg = obsmetrics.registry()
        e1 = _engine()
        snap = reg.snapshot()
        e1.run(QS)
        e1.run(QS)                         # warm: hits
        win1 = reg.since(snap)
        hits1 = sum(v for (name, labels), v in win1.items()
                    if name == "cache_hits_total")
        assert hits1 > 0
        # a second engine's traffic lands on different cache labels and
        # in a different window
        snap2 = reg.snapshot()
        e2 = _engine()
        e2.run(QS)
        win2 = reg.since(snap2)
        lbl1 = {labels for (name, labels), _ in win1.items()
                if name.startswith("cache_")}
        lbl2 = {labels for (name, labels), _ in win2.items()
                if name.startswith("cache_")}
        assert lbl1 and lbl2 and lbl1.isdisjoint(lbl2)

    def test_query_latency_histogram_recorded(self):
        reg = obsmetrics.registry()
        snap = reg.snapshot()
        _engine().run(QS)
        win = reg.since(snap)
        lat = [w for (name, labels), w in win.items()
               if name == "query_latency_s"]
        assert lat and lat[0].count >= len(QS)
        assert [w for (name, labels), w in win.items()
                if name == "engine_batch_wall_s"]

    def test_session_trace_kwarg_and_tracer_property(self, tmp_path):
        sess = PathSession(circulant(), trace=True)
        assert sess.tracer is obstrace.tracer()
        sess.tracer.reset()
        sess.run(QS)
        doc = sess.tracer.export(tmp_path / "s.json")
        assert "engine.run" in obstrace.stage_names(doc)
        # trace=None defers to config default (off)
        sess2 = PathSession(circulant())
        assert sess2.engine.cfg.trace is False

    def test_apply_delta_span_and_stats(self):
        from repro.core import GraphDelta
        eng = _engine(trace=True)
        eng.run(QS)
        eng.obs.reset()
        rep = eng.apply_delta(GraphDelta.from_pairs(add=[(20, 27)]))
        assert rep["t_apply_s"] > 0
        assert "engine.apply_delta" in {s.name for s in eng.obs.spans()}


# ----------------------------------------------------------------------
# serving integration (incl. the serve_batch aliasing fix)
# ----------------------------------------------------------------------
class TestServing:
    def test_serve_batch_info_is_mutation_safe(self):
        # regression: serve_batch returned a shallow copy whose nested
        # dicts (cache info, per-device stats) later batches kept mutating
        from repro.launch.serve import serve_batch
        eng = _engine()
        results, info = serve_batch(eng, QS)
        assert set(results) == {0, 1, 2}
        frozen = json.loads(json.dumps(info, default=str))
        # run more traffic through the same engine/cache, then mutate the
        # live cache info dict the old shallow copy would have aliased
        serve_batch(eng, [(1, 4, 3), (9, 12, 3)])
        if eng.cache is not None:
            eng.cache.info()["entries"] = -1
        assert json.loads(json.dumps(info, default=str)) == frozen

    def test_streaming_batch_log_latency_fields(self):
        sess = PathSession(circulant())
        for q in QS:
            sess.submit(q)
        res = sess.results()
        assert len(res) == len(QS)
        entry = sess.batch_log[-1]
        for k in ("t_assemble_s", "admission_wait_p50_s",
                  "admission_wait_max_s", "e2e_p50_s", "e2e_p99_s"):
            assert k in entry and entry[k] >= 0.0, k
        assert entry["e2e_p99_s"] >= entry["e2e_p50_s"]
        # admission wait + e2e histograms landed in the process registry
        reg = obsmetrics.registry()
        assert reg.histogram("serve_query_e2e_s").count >= len(QS)
        assert reg.histogram("serve_admission_wait_s").count >= len(QS)

    def test_traced_streaming_has_serve_spans(self):
        sess = PathSession(circulant(), trace=True)
        sess.tracer.reset()
        for q in QS:
            sess.submit(q)
        sess.results()
        names = {s.name for s in sess.tracer.spans()}
        assert {"serve.batch", "serve.assemble", "engine.run"} <= names


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------
class TestCli:
    def _write_trace(self, tmp_path):
        tr = obstrace.Tracer(enabled=True)
        with tr.span("engine.run"):
            with tr.span("enumerate.level", level=0):
                pass
        p = tmp_path / "t.json"
        tr.export(p)
        return p

    def test_summarize_command(self, tmp_path, capsys):
        from repro.obs.__main__ import main
        p = self._write_trace(tmp_path)
        assert main(["summarize", str(p)]) == 0
        out = capsys.readouterr().out
        assert "enumerate.level" in out and "coverage" in out

    def test_export_filter(self, tmp_path):
        from repro.obs.__main__ import main
        p = self._write_trace(tmp_path)
        out = tmp_path / "f.json"
        assert main(["export", str(p), "-o", str(out),
                     "--filter", "enumerate."]) == 0
        doc = obstrace.load(out)
        assert obstrace.stage_names(doc) == {"enumerate.level"}

    def test_summarize_empty_trace_fails(self, tmp_path):
        from repro.obs.__main__ import main
        p = tmp_path / "empty.json"
        p.write_text('{"traceEvents": []}')
        assert main(["summarize", str(p)]) == 1


# ----------------------------------------------------------------------
# compile spans and counts from jax.monitoring (core.compilelog)
# ----------------------------------------------------------------------
class TestCompileEvents:
    def test_fresh_jit_records_three_phase_spans_under_caller(self):
        import jax
        import jax.numpy as jnp
        from repro.core import compilelog

        log = compilelog.enable()
        x = jax.block_until_ready(jnp.arange(5))
        tr = obstrace.enable().reset()

        @jax.jit
        def fresh_fn(v):          # a new function: a cold jit cache
            return v * 3 + 1

        snap = log.snapshot()
        with tr.span("caller") as caller:
            jax.block_until_ready(fresh_fn(x))
        comp = [s for s in tr.spans() if s.name.startswith("compile.")]
        assert sorted(s.name for s in comp) == \
            ["compile.backend", "compile.lower", "compile.trace"]
        for s in comp:
            assert s.depth == caller.depth + 1, s.name
            assert caller.t0 <= s.t0 <= s.t1 <= caller.t1, s.name
        assert log.since(snap) == {"fresh_fn": 1}

        n = len(tr.spans())
        with tr.span("caller"):
            jax.block_until_ready(fresh_fn(x))     # warm: no compile
        assert not [s for s in tr.spans()[n:]
                    if s.name.startswith("compile.")]
        assert log.since(snap) == {"fresh_fn": 1}

    def test_count_matches_jax_compile_log_over_serving(self):
        """With ``jax_log_compiles`` on for this test only, the listener
        counts exactly the ``Compiling ... with global shapes`` records
        over the recompile harness's different-query batches, served."""
        import logging
        import re

        import jax
        from repro.core import compilelog

        pattern = re.compile(r"Compiling jit\(([^\s()]+)\) with global shapes")
        logged = []

        class Grab(logging.Handler):
            def emit(self, record):
                m = pattern.match(record.getMessage())
                if m:
                    logged.append(m.group(1))

        n = 56                    # shapes no other test here compiles
        log = compilelog.enable()
        sess = PathSession(circulant(n), EngineConfig(min_cap=256))
        logger = logging.getLogger("jax._src.interpreters.pxla")
        grab = Grab(level=logging.DEBUG)
        logger.addHandler(grab)
        jax.config.update("jax_log_compiles", True)
        try:
            snap = log.snapshot()
            for i in range(3):
                for j in range(6):
                    sess.submit((8 * j + i, (8 * j + i + 3) % n, 3))
                sess.results()
            got = log.since(snap)
        finally:
            jax.config.update("jax_log_compiles", False)
            logger.removeHandler(grab)
        assert logged, "the cold batch must compile"
        assert sum(got.values()) == len(logged)
        assert got == dict(Counter(logged))


# ----------------------------------------------------------------------
# host-sync and retry counters per search node
# ----------------------------------------------------------------------
class TestSearchCounters:
    KEYS = ("n_nodes", "n_node_syncs", "n_assemble_syncs", "n_retries")

    def test_host_read_counts_one_round_trip_per_value(self):
        import jax.numpy as jnp

        eng, c = _engine(), obsmetrics.Counter()
        x = jnp.arange(4) + 1               # a device value
        assert eng._host(x, c).tolist() == [1, 2, 3, 4] and c.value == 1
        eng._host(x, c)                     # JAX keeps the host copy
        eng._host(np.arange(3), c)          # already on the host
        assert c.value == 1
        eng._host(x + 1, c)                 # a new device value
        assert c.value == 2

    def test_run_and_batch_log_report_node_counters(self):
        eng = _engine(cache_bytes=0)
        reg = obsmetrics.registry()
        snap = reg.snapshot()
        st = eng.run(QS).stats
        assert st["n_nodes"] > 0
        assert st["n_node_syncs"] >= st["n_nodes"]
        assert st["n_assemble_syncs"] > 0
        win = reg.since(snap)
        assert win[("engine_nodes_total", ())] == st["n_nodes"]
        assert win[("engine_host_syncs_total", (("stage", "node"),))] == \
            st["n_node_syncs"]

        sess = PathSession(circulant(), EngineConfig(min_cap=256))
        for q in QS:
            sess.submit(q)
        sess.results()
        entry = sess.batch_log[-1]
        assert all(k in entry for k in self.KEYS)
        assert entry["n_nodes"] > 0
        assert entry["n_node_syncs"] >= entry["n_nodes"]

    def test_tiny_caps_force_counted_retries(self):
        want = _engine(cache_bytes=0).run(QS)
        tiny = _engine(cache_bytes=0, min_cap=1, join_cap=1,
                       plan_caps=False)
        r = tiny.run(QS)
        assert r.stats["n_retries"] > 0
        for qi in range(len(QS)):
            assert path_set(r[qi].paths) == path_set(want[qi].paths)
