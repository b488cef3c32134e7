"""``hist_live_row_pct`` over hand-made ``run`` dicts: a reading from the
scan's counters, and ``None`` from a program that does not count live
rows (the parent commit's case)."""

import pytest

from benchmark import run as bench_run

# 15 trees of 5 waves over 20,000,000 rows: the root wave finds the bag
# (0.8 of the rows), the four later ones the smaller children
WINDOW = {"span_n.train.chunk": 3, "grow.trees": 15, "grow.waves": 75,
          "grow.rows_real": 75 * 20_000_000,
          "grow.rows_scanned": 75 * 6_815_744,
          "grow.rows_live": 15 * (16_000_000 + 4 * 4_250_000)}


def _read(window):
    read = bench_run.load_plugin("layer_metrics", "hist_live_row_pct").read
    return read({"setup_counters": {}, "window_counters": dict(window)})


def test_reading():
    assert _read(WINDOW) == pytest.approx(100.0 * 33 / 100, rel=1e-12)


def test_silent_without_the_counter():
    parent = {k: v for k, v in WINDOW.items() if k != "grow.rows_live"}
    assert _read(parent) is None
    assert _read({"grow.hist.einsum_bf16": 1}) is None


def test_the_benchmark_lists_it_once_in_every_training_cell():
    bench = bench_run.load_json("BENCHMARK.json")
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "hist_live_row_pct"]
    assert entry == {"name": "hist_live_row_pct", "unit": "%",
                     "better": "lower", "source": "program_counter",
                     "layer": "growth programs",
                     "moves": "train_trees_per_s"}
