"""A checkout of the benchmark with one tiny extra cell, for CPU tests.

``make_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` (without
caches and traces), links the program's sources, and adds the cell
``tiny.shared``: a 4,096-vertex random geometric graph, batches of 16 at
k=4, one warm-up batch. Every file of the tiny cell is new; no existing file
is edited.
"""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_CONFIG = {"name": "tiny", "generator": "rgg",
               "params": {"seed": 5, "n": 4096, "radius_c": 0.55}}
TINY_MIX = {"batch": 16, "k": 4, "outputs": ["paths", "count", "exists"],
            "walk": [2, "k"],
            "shared": {"share": 0.8, "seeds_per_batch": 2, "perturb_p": 0.5},
            "warm_batches": 1, "window_batches": 2}


def make_root(tmp: Path) -> Path:
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench", ignore=shutil.
                    ignore_patterns(".jax_cache", ".traces", "__pycache__",
                                    ".pytest_cache"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.shared", "config": "tiny",
                               "traffic": "tiny-shared", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    (root / "bench/traffic/tiny-shared.json").write_text(
        json.dumps(TINY_MIX))
    return root
