"""The index sweep's rows-per-arc reader on a synthetic window: the ratio
of the summed counters, and nothing read where the batches carry none."""
import pytest

import harness
from tiny import REPO


def _read(batches):
    ctx = harness.WindowContext(window_s=10.0, n_answered=64, spans=[],
                                batches=list(batches), compiles_in_window=0,
                                index_bytes=0, peaks={})
    return harness.load_reader(REPO, "msbfs.rows_per_arc")(ctx)


def test_rows_per_arc_sums_over_batches():
    batches = [{"n_index_rows": 110, "n_index_arcs": 100},
               {"n_index_rows": 420, "n_index_arcs": 400}]
    assert _read(batches) == pytest.approx(530 / 500)


def test_rows_per_arc_none_without_counters():
    assert _read([{"n_nodes": 3}, {"n_nodes": 4}]) is None
    assert _read([]) is None
