"""``root_shared_pct`` over hand-made ``run`` dicts and the tiny multiclass
rehearsal: a reading from the scan's counters, and ``None`` from a
program that does not count the roots it shares (the parent commit's
case)."""

import pytest

from benchmark import run as bench_run
from benchmark.tests import rehearse_multiclass

# 4 dispatches of one iteration of 100 class trees
WINDOW = {"span_n.train.chunk": 4, "grow.trees": 400,
          "grow.class_trees": 400, "grow.root_shared": 400}


def _read(window):
    read = bench_run.load_plugin("layer_metrics", "root_shared_pct").read
    return read({"setup_counters": {}, "window_counters": dict(window)})


def test_reading():
    assert _read(WINDOW) == pytest.approx(100.0, rel=1e-12)
    assert _read({**WINDOW, "grow.root_shared": 0}) == 0.0
    assert _read({**WINDOW, "grow.root_shared": 100}) \
        == pytest.approx(25.0, rel=1e-12)


def test_silent_without_the_counter():
    parent = {k: v for k, v in WINDOW.items() if k != "grow.root_shared"}
    assert _read(parent) is None
    assert _read({"grow.root_shared": 9}) is None
    assert _read({**WINDOW, "grow.class_trees": 0}) is None


def test_the_benchmark_lists_it_in_the_multiclass_cell():
    bench = bench_run.load_json("BENCHMARK.json")
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "root_shared_pct"]
    assert entry == {"name": "root_shared_pct", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "growth programs",
                     "moves": "train_trees_per_s",
                     "workloads": [rehearse_multiclass.CELL]}


def test_a_tiny_rehearsal_shares_every_root():
    kind = bench_run.load_plugin("kinds", "train_steady_multiclass")
    res = kind.run(rehearse_multiclass.tiny_context(
        seed=2**33 + 7, seconds=0.3, trace=False,
        limits={**rehearse_multiclass.cell_workload()["check"]["limits"],
                "gain_gap_rms": {"max": 0.5}}))
    c = res["run"]["window_counters"]
    assert c["grow.class_trees"] == res["run"]["window"]["trees"] > 0
    assert _read(c) == 100.0
