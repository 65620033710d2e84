"""``trace_reduce`` on hand-made events and on a trace recorded on a
TPU v5e (``data/``)."""
from pathlib import Path

import pytest

import trace_reduce as tr
from trace_reduce import Event

DATA = Path(__file__).resolve().parent / "data"


def _events():
    ms = 1e6      # nanoseconds in a millisecond
    return [
        Event("host", "bench.window", 0, 100 * ms),
        Event("host", "serve.assemble", 5 * ms, 40 * ms),
        Event("host", "enumerate.node", 50 * ms, 90 * ms),
        Event("host", "msbfs.level", 60 * ms, 70 * ms),
        # ops before the window are clipped away, overlaps count once
        Event("op", "fusion.1", -10 * ms, 10 * ms),
        Event("op", "fusion.2", 8 * ms, 30 * ms),
        Event("op", "gather.3", 55 * ms, 58 * ms),
        Event("op", "gather.4", 95 * ms, 120 * ms),
        Event("module", "jit_msbfs_dist_ell(12)", -10 * ms, 30 * ms),
        Event("module", "jit_expand_level(3)", 55 * ms, 58 * ms),
        Event("module", "jit_expand_level(4)", 95 * ms, 120 * ms),
    ]


def test_union_length():
    assert tr.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4


def test_reduce_by_hand():
    red = tr.reduce(_events(), "bench.window")
    # busy: [0, 30] + [55, 58] + [95, 100] = 38 ms of a 100 ms window
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.038)
    assert red.idle_share == pytest.approx(0.62)
    assert red.modules == pytest.approx({"msbfs_dist_ell": 0.030,
                                         "expand_level": 0.008})
    # idle gaps: [58, 95] has its midpoint, 76.5 ms, inside
    # enumerate.node alone; [30, 55] has its midpoint, 42.5 ms, after
    # serve.assemble ended, where no program span is open
    assert red.gaps == [("enumerate.node", pytest.approx(0.037)),
                        ("no host span", pytest.approx(0.025))]
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["msbfs_dist_ell", pytest.approx(0.030)]
    assert len(bd["idle_gaps"]) == 2


def test_nothing_to_read():
    assert tr.reduce([e for e in _events() if e.where != "op"],
                     "bench.window") is None
    assert tr.reduce(_events(), "no.such.span") is None


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: a jitted 1024x1024 matmul-sum run
    three times inside a ``serve.assemble`` annotation (the first of them
    lands before the annotation's start on the trace's clock) and once
    inside ``enumerate.node`` 50 ms later. Expected numbers read off the
    file's events by hand."""
    events = tr.load(DATA / "tpu_v5e_tiny.xplane.pb")
    hosts = sorted(e.name for e in events if e.where == "host")
    assert hosts == ["enumerate.node", "serve.assemble"]
    assert sum(e.where == "module" for e in events) == 4
    assert sum(e.where == "op" for e in events) == 12
    red = tr.reduce(events, "serve.assemble")
    # window 53,407,162 .. 55,645,482 ns; inside it, one execution of
    # jit_f: ops 53,690,076-089, 090-093, 093-53,701,920 (union 11,843 ns)
    assert red.window_s == pytest.approx(2_238_320e-9)
    assert red.busy_s == pytest.approx(11_843e-9)
    assert red.modules == pytest.approx({"f": 11_849e-9})
    assert [g[1] for g in red.gaps[:2]] == pytest.approx(
        [1_943_562e-9, 282_914e-9])
    assert red.gaps[0][0] == "no host span"
