"""``hist_gathered_wave_pct`` over hand-made ``run`` dicts: a reading
from the scan's counters, and ``None`` from a program that does not
count the waves it compacts (the parent commit's case)."""

import pytest

from benchmark import run as bench_run

# 20 trees of 5 waves: every wave but the root's compacts its live rows
WINDOW = {"span_n.train.chunk": 4, "grow.trees": 20, "grow.waves": 100,
          "grow.waves_gathered": 80, "grow.rows_real": 100 * 20_000_000,
          "grow.rows_live": 20 * 38_000_000}


def _read(window):
    read = bench_run.load_plugin("layer_metrics",
                                 "hist_gathered_wave_pct").read
    return read({"setup_counters": {}, "window_counters": dict(window)})


def test_reading():
    assert _read(WINDOW) == pytest.approx(80.0, rel=1e-12)
    assert _read({**WINDOW, "grow.waves_gathered": 0}) == 0.0


def test_silent_without_the_counter():
    parent = {k: v for k, v in WINDOW.items()
              if k != "grow.waves_gathered"}
    assert _read(parent) is None
    assert _read({"grow.waves_gathered": 3}) is None
    assert _read({"grow.hist.einsum_bf16": 1}) is None


def test_the_benchmark_lists_it_once_in_every_training_cell():
    bench = bench_run.load_json("BENCHMARK.json")
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "hist_gathered_wave_pct"]
    assert entry == {"name": "hist_gathered_wave_pct", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "growth programs",
                     "moves": "train_trees_per_s"}
    assert bench["per_layer"][-1] is entry
