#!/usr/bin/env python3
"""Benchmark entry: run one cell of ``BENCHMARK.json`` once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress on standard error, then the compared numbers beside their
limits as its last lines there, and as the last line of standard output
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, traced, ``breakdown``; ``checks`` comes last. It exits
with a non-zero code and prints no result when JAX finds no TPU, fewer
chips than the cell asks for, or no program sources beside ``bench/``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:8.3f}s] {msg}", file=sys.stderr,
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    sys.path.insert(0, str(BENCH))
    import harness

    try:
        line, checks = harness.run(BENCH.parent, args.workload, args.seed,
                                   args.seconds, bool(args.trace), T_START,
                                   say=say)
    except RuntimeError as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    for name, c in checks.items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
