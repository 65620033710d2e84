"""A configuration, a traffic mix and a per-layer metric are found from
new files and new ``BENCHMARK.json`` entries alone."""
import json
import time

import harness
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
