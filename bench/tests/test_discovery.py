"""A configuration, its graph generator and engine settings, a traffic mix
and a per-layer metric are found from new files and new ``BENCHMARK.json``
entries alone."""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import harness
from generators import graphs
from tiny import TINY_CONFIG, TINY_MIX, make_root

READER = '''"""Batches in the window."""


def read(ctx):
    return len(ctx.batches) or None
'''


def test_new_files_are_found_by_name(tmp_path):
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench/configs/rgg-2k.json").write_text(json.dumps(
        dict(TINY_CONFIG, name="rgg-2k",
             params={"seed": 2, "n": 2048, "radius_c": 0.6})))
    (root / "bench/traffic/random16-k3.json").write_text(json.dumps(
        dict(TINY_MIX, k=3, shared=None)))
    (root / "bench/metrics/serve.batches.py").write_text(READER)
    bench["configs"].append({"name": "rgg-2k", "source": "test",
                             "file": "bench/configs/rgg-2k.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "rgg2k.random16", "config": "rgg-2k",
                               "traffic": "random16-k3", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "serve.batches", "unit": "batches",
                               "better": "higher", "source": "program_counter",
                               "layer": "serving", "moves": "qps"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # nothing that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())

    cell = harness.load_cell(root, "rgg2k.random16")
    assert cell.config["params"]["n"] == 2048 and cell.mix["k"] == 3
    # every cell reports every metric
    for name in ("rgg2k.random16", "tiny.shared"):
        assert "serve.batches" in [
            m["name"] for m in harness.load_cell(root, name).per_layer]

    line, checks = harness.run(root, "rgg2k.random16", 5, 1.0, True,
                               time.perf_counter(), require_tpu=False)
    assert line["correct"] is True
    assert line["metrics"]["serve.batches"]["value"] >= 1
    assert line["metrics"]["serve.batches"]["unit"] == "batches"
    assert line["metrics"]["device.compiles_in_window"]["value"] >= 0


GENERATOR = '''"""A square lattice of side ``side`` with vertex labels
permuted from ``seed``; every edge both ways."""
import numpy as np


def make(seed, side):
    n = side * side
    v = np.arange(n).reshape(side, side)
    a = np.concatenate([v[:, :-1].ravel(), v[:-1, :].ravel()])
    b = np.concatenate([v[:, 1:].ravel(), v[1:, :].ravel()])
    perm = np.random.default_rng(seed).permutation(n)
    a, b = perm[a].astype(np.int64), perm[b].astype(np.int64)
    return n, np.concatenate([a, b]), np.concatenate([b, a])
'''


def _add_cell(root, config, generator=None):
    """Add the configuration ``config`` (a file of its own, named by
    ``config["name"]``), optionally the generator file ``bench/graphs/
    <config["generator"]>.py``, and the cell ``<name>.random16`` over a
    traffic file of its own. Returns the cell's name."""
    name = config["name"]
    if generator is not None:
        (root / "bench/graphs").mkdir(exist_ok=True)
        (root / f"bench/graphs/{config['generator']}.py").write_text(
            generator)
    (root / f"bench/configs/{name}.json").write_text(json.dumps(config))
    (root / f"bench/traffic/{name}-random16.json").write_text(json.dumps(
        dict(TINY_MIX, k=3, shared=None)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{name}.random16", "config": name,
                               "traffic": f"{name}-random16", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return f"{name}.random16"


def test_new_generator_and_engine_settings(tmp_path, monkeypatch):
    """A configuration with a generator file of its own, EngineConfig
    overrides and a planner runs through the harness and is correct, and
    the engine and server the harness built carry its settings."""
    import repro.launch.serve as serve
    from repro.core.engine import EngineConfig
    from repro.core.query import Planner

    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*")
              if p.is_file() and p.name != "BENCHMARK.json"}
    bench_before = json.loads((root / "BENCHMARK.json").read_text())
    cell = _add_cell(root, {"name": "lattice", "generator": "lattice",
                            "params": {"seed": 2**31 + 7, "side": 48},
                            "engine_config": {"cache_bytes": 1048576},
                            "planner": "AUTO"}, GENERATOR)
    assert all(p.read_bytes() == b for p, b in before.items())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key, value in bench_before.items():     # entries only added
        assert (bench[key][:len(value)] if isinstance(value, list)
                else bench[key]) == value

    built = []

    class Recording(serve.StreamingServer):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            built.append(self)

    monkeypatch.setattr(serve, "StreamingServer", Recording)
    said = []
    line, checks = harness.run(root, cell, 2**31 + 11, 1.0, False,
                               time.perf_counter(), require_tpu=False,
                               say=said.append)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and checks["missing"]["value"] == 0
    (srv,) = built
    assert srv.planner is Planner.AUTO
    assert srv.engine.cfg == EngineConfig(cache_bytes=1048576)
    assert srv.engine.cache is not None
    graph_line = next(s for s in said if s.startswith("graph:"))
    assert "engine_config {'cache_bytes': 1048576}, planner AUTO" in graph_line
    assert graph_line.startswith("graph: n=2304 ")


def test_committed_cells_keep_their_settings():
    """The committed configurations state no settings: the harness builds
    the engine's defaults under Planner.BATCH for them."""
    from repro.core.query import Planner

    root = Path(__file__).resolve().parents[2]
    for w in json.loads((root / "BENCHMARK.json").read_text())["workloads"]:
        cell = harness.load_cell(root, w["name"])
        assert harness.engine_settings(cell.config,
                                       Planner) == ({}, Planner.BATCH)


@pytest.mark.parametrize("generator,files,match", [
    ("nope", [], r"unknown graph generator 'nope'; built-in: \['delaunay', "
                 r"'rgg'\], files in .*: \['lattice'\]"),
    ("rgg", ["rgg"], "both a built-in and the file"),
], ids=["unknown", "builtin-and-file"])
def test_generator_name_is_refused(tmp_path, generator, files, match):
    (tmp_path / "lattice.py").write_text(GENERATOR)
    for f in files:
        (tmp_path / f"{f}.py").write_text(GENERATOR)
    with pytest.raises(ValueError, match=match):
        graphs.make({"generator": generator, "params": {"seed": 0, "n": 64,
                                                        "radius_c": 0.5}},
                    tmp_path)


def test_generator_file_is_found(tmp_path):
    (tmp_path / "lattice.py").write_text(GENERATOR)
    n, src, dst = graphs.make({"generator": "lattice",
                               "params": {"seed": 3, "side": 4}}, tmp_path)
    assert n == 16 and src.size == dst.size == 2 * 24
    assert src.dtype == dst.dtype == np.int64


@pytest.mark.parametrize("settings,match", [
    ({"engine_config": {"no_such_field": 1}}, "'no_such_field'"),
    ({"engine_config": {"trace": True}}, "'trace'"),
    ({"engine_config": {"trace_fence": True}}, "'trace_fence'"),
    ({"engine_config": {"trace_annotations": True}}, "'trace_annotations'"),
    ({"engine_config": {"mesh": None}}, "'mesh'"),
    ({"engine_config": {"n_devices": 4}}, "'n_devices'"),
    ({"engine_config": {"backend": "jnp"}}, "'backend'"),
    ({"engine_config": {"router": None}}, "'router'"),
    ({"engine_config": {"kernel_backend": "jnp"}}, "'kernel_backend'"),
    ({"engine_config": {"kernel_backend": "interpret"}}, "'kernel_backend'"),
    ({"engine_config": {"delta_backend": "msbfs"}}, "'delta_backend'"),
    ({"engine_config": {"plan_caps": False}}, "'plan_caps'"),
    ({"engine_config": {"gamma": 0.25}}, "'gamma'"),
    ({"engine_config": {"cache_bytes": [1]}}, "'cache_bytes'"),
    ({"engine_config": {"cache_bytes": "1MB"}}, "'cache_bytes'"),
    ({"engine_config": {"cache_bytes": True}}, "'cache_bytes'"),
    ({"engine_config": {"cache_bytes": 1.5}}, "'cache_bytes'"),
    ({"engine_config": [["cache_bytes", 1]]}, "engine_config must be"),
    ({"planner": "FAST"}, "planner 'FAST'"),
    ({"planner": "batch"}, "planner 'batch'"),
], ids=["unknown-field", "trace", "trace_fence", "trace_annotations", "mesh",
        "n_devices", "backend", "router", "kernel_backend-jnp",
        "kernel_backend-interpret", "delta_backend", "plan_caps", "gamma",
        "list-value", "str-for-int", "bool-for-int", "float-for-int",
        "not-an-object", "unknown-planner", "planner-value-not-name"])
def test_engine_settings_are_refused(tmp_path, settings, match):
    """A configuration that sets an EngineConfig field outside the
    harness's settings (one the harness sets itself, an implementation
    choice such as the kernel backend, or no field at all), gives a
    setting a value of another type, or names no Planner, is refused by
    the harness before it serves."""
    root = make_root(tmp_path)
    cell = _add_cell(root, dict(TINY_CONFIG, name="bad", **settings))
    with pytest.raises(ValueError, match=match):
        harness.run(root, cell, 1, 1.0, False, time.perf_counter(),
                    require_tpu=False)
