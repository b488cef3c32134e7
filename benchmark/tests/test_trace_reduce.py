"""``trace_reduce`` against the small trace recorded on the v5e by
``record_trace.py`` (three 2-tree dispatches with a 5 ms sleep between),
and against hand-made intervals."""

import os

import pytest

from benchmark import trace_reduce as tr

SAMPLE = os.path.join(os.path.dirname(__file__), "data", "sample.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(SAMPLE)


def test_window_and_busy(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.036447847, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.007846856, rel=1e-6)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_idle_gaps_are_labelled_by_the_open_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) <= {"dispatch", "block_until_ready",
                         tr.NO_SPAN}
    # the recorder slept between dispatches: most idle time is there
    assert max(gaps, key=gaps.get) == tr.NO_SPAN
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_spans_and_top_ops(reduced):
    names = [s["name"] for s in reduced["spans"]]
    assert names == ["dispatch", "block_until_ready"] * 3
    assert all(0 <= s["busy_s"] <= s["dur_s"] for s in reduced["spans"])
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= 10
    assert all(" = " not in name for name, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    # self times never add up to more than the busy time
    assert sum(s for _, s in ops) <= reduced["busy_s"] * (1 + 1e-9)


def test_union_and_self_time_by_hand():
    assert tr._union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [[0, 3], [5, 6]]
    # a while of 10 s holding two bodies of 3 s and 4 s
    ev = [("while.1", 0.0, 10e9), ("fusion.1", 1e9, 4e9),
          ("fusion.2", 5e9, 9e9), ("copy.1", 11e9, 12e9)]
    st = tr.self_times(ev)
    assert st == pytest.approx({"while.1": 3.0, "fusion.1": 3.0,
                                "fusion.2": 4.0, "copy.1": 1.0})
    assert tr.op_name("%fusion.12 = f32[8]{0} fusion(%a), kind=kLoop") \
        == "fusion.12"
