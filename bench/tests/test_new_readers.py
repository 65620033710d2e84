"""The compile-share and search-counter readers on a synthetic window:
values by hand, nested compile spans counted once, and nothing read where
the window has no spans or no batches."""
import pytest

import harness
from tiny import REPO


def _read(name, ctx):
    return harness.load_reader(REPO, name)(ctx)


def _ctx(spans=(), batches=(), n_answered=0, compiles=0):
    return harness.WindowContext(window_s=10.0, n_answered=n_answered,
                                 spans=list(spans), batches=list(batches),
                                 compiles_in_window=compiles, index_bytes=0,
                                 peaks={})


def test_compile_share_is_the_union_of_nested_spans():
    spans = [("join.keyed", 0.0, 3.0),
             ("compile.trace", 1.0, 1.5),
             ("compile.trace", 1.1, 1.2),        # nested: counted once
             ("compile.lower", 1.5, 1.8),
             ("compile.backend", 1.8, 2.5),
             ("compile.backend", 2.4, 2.6),      # overlaps the one before
             ("compile.backend", 5.0, 5.4)]
    assert _read("compile.share", _ctx(spans, compiles=3)) == \
        pytest.approx(100.0 * (1.6 + 0.4) / 10.0)


def test_compile_share_reads_zero_without_compiles_and_none_untraced():
    assert _read("compile.share", _ctx([("serve.batch", 0.0, 9.0)])) == 0.0
    # compiles counted but no spans of them: a program without them
    assert _read("compile.share",
                 _ctx([("serve.batch", 0.0, 9.0)], compiles=4)) is None
    assert _read("compile.share", _ctx()) is None


def test_search_counters_per_node_and_per_query():
    batches = [{"n_nodes": 10, "n_node_syncs": 120, "n_retries": 1,
                "n_assemble_syncs": 40},
               {"n_nodes": 30, "n_node_syncs": 280, "n_retries": 0,
                "n_assemble_syncs": 24}]
    ctx = _ctx(batches=batches, n_answered=128)
    assert _read("enumerate.syncs_per_node", ctx) == pytest.approx(10.0)
    assert _read("enumerate.retries_per_node", ctx) == pytest.approx(0.025)
    assert _read("assemble.syncs_per_query", ctx) == pytest.approx(0.5)


@pytest.mark.parametrize("name", ["enumerate.syncs_per_node",
                                  "enumerate.retries_per_node",
                                  "assemble.syncs_per_query"])
def test_search_counters_read_nothing_without_them(name):
    assert _read(name, _ctx(n_answered=64)) is None
    # a program whose batch_log has no search counters
    old = [{"n_psi_nodes": 5, "n_materialized": 5}]
    assert _read(name, _ctx(batches=old, n_answered=64)) is None
