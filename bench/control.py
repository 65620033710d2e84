#!/usr/bin/env python3
"""The correctness control: the plain reference put in the program's place
with one stated guarantee broken, compared as a run compares the program.

The guarantee broken is the hop budget: the control answers every query
with budget k - 1, the off-by-one that a change to the forward/backward
split would make. It must read above the limits, so that a run whose
program made that error comes out not correct.

    python bench/control.py --workload <cell> --seeds 1 2 3 [--batches 6]

Builds the cell's graph, takes the first ``--batches`` window batches with
their queries in each seed's order, and prints one JSON line per seed with
the numbers the run compares. It needs no accelerator and touches no JAX.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from generators import graphs, queries as qgen  # noqa: E402
from reference import Adjacency, answer  # noqa: E402

__all__ = ["control_answer", "control_readings"]


def control_answer(adj: Adjacency, s: int, t: int, k: int, kind: str):
    """The reference's answer at hop budget k - 1."""
    return answer(adj, s, t, k - 1, kind)


def control_readings(root: Path, workload: str, seed: int, batches: int
                     ) -> dict:
    """The compared numbers of the control on ``batches`` window batches
    of ``workload`` at ``seed``."""
    cell = harness.load_cell(root, workload)
    n, src, dst = graphs.make(cell.config, root / "bench" / "graphs")
    adj = Adjacency.build(n, src, dst)
    pool = qgen.window_batches(adj, cell.mix, seed)
    window = [(q, control_answer(adj, *q)) for i in range(batches)
              for q in pool[i % len(pool)]]
    return harness.compare(adj, window, qgen.seeded(seed, qgen.SAMPLE))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--batches", type=int, default=6)
    args = ap.parse_args()
    for seed in args.seeds:
        found = control_readings(BENCH.parent, args.workload, seed,
                                 args.batches)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "limits": harness.CHECK_LIMITS, **found}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
