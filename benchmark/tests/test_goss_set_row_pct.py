"""``goss_set_row_pct``, the GOSS cell's share of the keyed rows a tree's
waves may scan: its entry in ``BENCHMARK.json``, its reader over hand-made
runs and over the kind rehearsed at 20,000 rows (three histogram chunks
of the tests' 8,192 rows, so the fused scan gathers each tree's row set
once), and nothing on a program without the counter."""

import pytest

from benchmark import run as bench_run
from benchmark.tests import rehearse_goss

BENCH = bench_run.load_json("BENCHMARK.json")
NAME = "goss_set_row_pct"


def read(run):
    return bench_run.load_plugin("layer_metrics", NAME).read(run)


def test_the_benchmark_lists_it_for_the_goss_cell_alone():
    entries = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1
    m = entries[0]
    assert m["workloads"] == [rehearse_goss.CELL]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == ("%", "lower", "program_counter", "growth programs",
            "train_trees_per_s")


@pytest.mark.parametrize("counters", [
    {"grow.trees": 10},
    {"grow.goss_keys": 12_184_290, "grow.goss_top": 609_300},
    {"grow.goss_keys": 0, "grow.goss_set_rows": 0},
], ids=["no_goss", "no_set_counter", "no_sampled_tree"])
def test_nothing_without_its_counters(counters):
    assert read({"window_counters": counters}) is None


def test_over_a_hand_made_run():
    keys = 25 * 12_184_290
    run = {"window_counters": {"grow.goss_keys": keys,
                               "grow.goss_top": 25 * 609_300,
                               "grow.goss_sampled": 25 * 609_000,
                               "grow.goss_set_rows": 25 * 1_302_000}}
    assert read(run) == pytest.approx(100 * 1_302_000 / 12_184_290)


def test_the_tiny_run_reads_a_tenth_and_a_little_more():
    kind = bench_run.load_plugin("kinds", "train_steady_goss")
    res = kind.run(rehearse_goss.tiny_context(
        seed=2**31 + 7, seconds=0.3, trace=True,
        config=rehearse_goss.tiny_config(rows=20000)))
    assert res["correct"], res["compared"]
    cell = {"name": rehearse_goss.CELL, "chips": 1}
    line = bench_run.result_line(BENCH, cell, res,
                                 {"platform": "cpu", "kind": "cpu",
                                  "count": 1}, trace=True)
    got = line["metrics"]
    assert got["goss_rows_pct"]["value"] \
        <= got[NAME]["value"] < 12.0
