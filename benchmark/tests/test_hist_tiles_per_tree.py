"""``hist_tiles_per_tree`` over hand-made ``run`` dicts: a reading from
the scan's counters, and ``None`` from a program that does not count the
tiles it contracts (the parent commit's case)."""

import pytest

from benchmark import run as bench_run

# 15 trees of 8 waves on the ladder 4/4/4/16/16/32/64/128 of three stat
# columns: seven waves of one tile and a closing wave of two
WINDOW = {"span_n.train.chunk": 3, "grow.trees": 15, "grow.waves": 120,
          "grow.wave_slots": 4020, "grow.hist_tiles": 135}


def _read(window):
    read = bench_run.load_plugin("layer_metrics",
                                 "hist_tiles_per_tree").read
    return read({"setup_counters": {}, "window_counters": dict(window)})


def test_reading():
    assert _read(WINDOW) == pytest.approx(9.0, rel=1e-12)
    assert _read({**WINDOW, "grow.hist_tiles": 165}) \
        == pytest.approx(11.0, rel=1e-12)


def test_silent_without_the_counter():
    parent = {k: v for k, v in WINDOW.items() if k != "grow.hist_tiles"}
    assert _read(parent) is None
    assert _read({"grow.hist_tiles": 9}) is None
    assert _read({**WINDOW, "grow.trees": 0}) is None


def test_the_benchmark_lists_it_once_in_every_training_cell():
    bench = bench_run.load_json("BENCHMARK.json")
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "hist_tiles_per_tree"]
    assert entry == {"name": "hist_tiles_per_tree", "unit": "tiles",
                     "better": "lower", "source": "program_counter",
                     "layer": "growth programs",
                     "moves": "train_trees_per_s"}
