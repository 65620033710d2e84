"""Whole runs of the harness on the CPU at a tiny size, with the look for
a chip skipped: a sound run is correct, and a run whose served path is
broken underneath comes out not correct, once for each fault a cell of
this benchmark can have. Also the control, and the exits without a
result."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness
from control import control_readings
from tiny import REPO, make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def _run(root, seed=2**33 + 5, trace=False):
    return harness.run(root, "tiny.shared", seed, 1.0, trace,
                       time.perf_counter(), require_tpu=False)


def test_sound_run_is_correct(root):
    line, checks = _run(root)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"qps", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert set(checks) == set(harness.CHECK_LIMITS)
    assert all(c["value"] == 0 for c in checks.values())


def _alter_count(monkeypatch):
    from repro.core.engine import BatchPathEngine
    from repro.core.query import Output

    wrap = BatchPathEngine._wrap

    def bad(q, payload):
        return wrap(q, payload + 1 if q.output is Output.COUNT else payload)
    monkeypatch.setattr(BatchPathEngine, "_wrap", staticmethod(bad))
    return "wrong_counts"


def _alter_exists(monkeypatch):
    from repro.core.engine import BatchPathEngine
    from repro.core.query import Output

    wrap = BatchPathEngine._wrap

    def bad(q, payload):
        r = wrap(q, payload)
        if q.output is Output.EXISTS:
            r._exists = not r._exists
        return r
    monkeypatch.setattr(BatchPathEngine, "_wrap", staticmethod(bad))
    return "wrong_exists"


def _alter_paths(monkeypatch):
    from repro.core.query import PathsStore

    host = PathsStore.host
    monkeypatch.setattr(PathsStore, "host",
                        property(lambda self: host.fget(self)[1:]))
    return "wrong_paths"


def _drop_half_batch(monkeypatch):
    from repro.launch.serve import StreamingServer

    admit = StreamingServer._admit

    def bad(self):
        qids = [w.qid for w in self._waiting]
        admit(self)
        for qid in qids[::2]:
            self.results.pop(qid, None)
    monkeypatch.setattr(StreamingServer, "_admit", bad)
    return "missing"


@pytest.mark.parametrize("fault", [_alter_count, _alter_exists,
                                   _alter_paths, _drop_half_batch],
                         ids=["count", "exists", "paths", "half_batch"])
def test_broken_path_is_not_correct(root, monkeypatch, fault):
    which = fault(monkeypatch)
    line, checks = _run(root, seed=2**34 + 7)
    assert line["correct"] is False
    assert checks[which]["value"] > checks[which]["limit"]


def test_control_is_not_correct(root):
    """The reference at hop budget k - 1 in the program's place fails a
    limit on every seed tried."""
    for seed in (1, 2**32 + 9, 77):
        found = control_readings(root, "tiny.shared", seed, batches=3)
        assert any(found[k] > lim for k, lim in harness.CHECK_LIMITS.items())


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny.shared",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result(root):
    p = _cli(root)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rgg.shared64",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no program sources" in p.stderr


def test_result_line_shape(root):
    """The result line's keys in their order, the checks last."""
    line, checks = _run(root, seed=3)
    line["checks"] = checks
    text = json.dumps(line)
    assert list(json.loads(text)) == ["correct", "attempted", "failed",
                                      "metrics", "device", "checks"]
