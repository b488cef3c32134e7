"""The GOSS cell (``allstate-goss.train``): its kind rehearsed at a few
thousand rows through the same code as a chip run, its three readers over
that run and over hand-made ones, the roofline of the selection, and the
chip's readings judged by the limits the cell ships with."""

import json
import os

import pytest

from benchmark import roofline, roofline_goss, run as bench_run
from benchmark.judge import compare
from benchmark.tests import probe_goss, rehearse_goss

BENCH = bench_run.load_json("BENCHMARK.json")
CELL = next(c for c in BENCH["workloads"] if c["name"] == rehearse_goss.CELL)
HERE = os.path.dirname(os.path.abspath(__file__))
LIMITS = rehearse_goss.cell_workload()["check"]["limits"]
NEW = ("goss_select_pct", "goss_select_roofline", "goss_rows_pct")


def reader(name):
    return bench_run.load_plugin("layer_metrics", name).read


@pytest.fixture(scope="module")
def tiny_result():
    kind = bench_run.load_plugin("kinds", "train_steady_goss")
    return kind.run(rehearse_goss.tiny_context(seed=2**31 + 5, seconds=0.3,
                                               trace=True))


def test_tiny_run_is_correct_by_the_cells_own_limits(tiny_result):
    res = tiny_result
    assert res["correct"], res["compared"]
    assert set(LIMITS) == set(res["compared"])
    run = res["run"]
    for key in ("window_counters", "shapes", "window", "trace",
                "device_kind", "scopes", "gauges"):
        assert key in run, key
    c = run["window_counters"]
    assert c["grow.goss_keys"] == 6000 * run["window"]["trees"]
    assert 300 * run["window"]["trees"] <= c["grow.goss_top"]


def test_result_line_holds_the_cells_metrics(tiny_result):
    cell = {"name": CELL["name"], "chips": 1}
    line = bench_run.result_line(BENCH, cell, tiny_result,
                                 {"platform": "cpu", "kind": "cpu",
                                  "count": 1}, trace=True)
    # the CPU's trace has no device plane: what reads one is left out
    assert 9.5 < line["metrics"]["goss_rows_pct"]["value"] < 11.0
    line = bench_run.result_line(BENCH, cell, tiny_result,
                                 {"platform": "cpu", "kind": "cpu",
                                  "count": 1}, trace=False)
    assert set(line["metrics"]) == {"train_trees_per_s", "setup_s"}


def test_the_cell_and_its_files_are_found_by_name():
    assert CELL["chips"] == 1 and CELL["config"] == "allstate-goss"
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL["name"]]
            assert callable(reader(m["name"]))
    cfg = rehearse_goss.cell_config()
    assert cfg["rows"] == 12_184_290 and cfg["features"] == 4228
    assert cfg["reduced"] == ["num_trees"]
    p = cfg["params"]
    assert (p["boosting"], p["top_rate"], p["other_rate"]) == \
        ("goss", 0.05, 0.05)
    onehot = rehearse_goss.rehearse_bundled.cell_config()
    assert {k: v for k, v in p.items()
            if k not in ("boosting", "top_rate", "other_rate")} \
        == onehot["params"]


def test_readers_return_nothing_on_a_program_without_their_sources():
    run = {"window_counters": {"grow.trees": 10}, "scopes": None,
           "device_kind": "TPU v5 lite"}
    for name in NEW:
        assert reader(name)(run) is None, name
    run["scopes"] = {"busy_s": 20.0, "lgb.wave_hist": {"self_s": 15.0}}
    run["window_counters"]["grow.goss_keys"] = 10
    assert reader("goss_select_pct")(run) is None
    assert reader("goss_select_roofline")(run) is None


def test_readers_over_a_hand_made_run():
    keys = 20 * 12_184_290
    run = {"window_counters": {"grow.goss_keys": keys,
                               "grow.goss_top": 20 * 609_300,
                               "grow.goss_sampled": 20 * 609_000},
           "scopes": {"busy_s": 20.0, "lgb.goss_select": {"self_s": 0.1}},
           "device_kind": "TPU v5 lite"}
    assert reader("goss_select_pct")(run) == pytest.approx(0.5)
    least = keys * 12 / 819e9
    assert roofline_goss.least_seconds(
        keys, roofline.peaks_for("TPU v5 lite")) == pytest.approx(least)
    assert reader("goss_select_roofline")(run) == pytest.approx(
        100 * least / 0.1)
    assert reader("goss_rows_pct")(run) == pytest.approx(
        100 * (609_300 + 609_000) / 12_184_290)


# --- the chip's readings, by the cell's own limits --------------------------

PROBE = os.path.join(HERE, "data", "probe_chip_goss.jsonl")
with open(PROBE) as f:
    RECORDS = [json.loads(line) for line in f if line.strip()]
PROBED = [r for r in RECORDS if r["how"] == "probed"]


def test_there_are_readings_of_the_cells_own_size():
    assert len(RECORDS) >= 6 and len(PROBED) >= 3
    assert len({r["seed"] for r in RECORDS}) == len(RECORDS)
    for rec in RECORDS:
        assert rec["notes"]["table"]["rows"] == 12_184_290
        assert rec["notes"]["goss"]["weights"] == [pytest.approx(19.0)]
        assert rec["readings"]["trees_checked"] == rec["notes"]["trees"]


@pytest.mark.parametrize("rec", RECORDS, ids=lambda r: str(r["seed"]))
def test_sound_run_is_correct(rec):
    judged = compare(rec["readings"], LIMITS)
    assert all(c["ok"] for c in judged.values()), judged


@pytest.mark.parametrize("rec", PROBED, ids=lambda r: str(r["seed"]))
def test_every_stand_in_is_not_correct(rec):
    verdicts = probe_goss.judge_stand_ins(rec["readings"], LIMITS)
    assert set(verdicts) == set(probe_goss.STAND_INS)
    for name, verdict in verdicts.items():
        if name == "gabs_top" and \
                rec["readings"]["gabs_top_goss_top_off"] == 0:
            # at 1% claims the rows of largest |g| and of largest |g*h|
            # are the same rows (a claim's |g| is near 1 and its |g*h|
            # near p): nothing to bite on here; tests/
            # test_allstate_goss.py sees it fail at 30% claims
            assert verdict["correct"]
            continue
        assert not verdict["correct"], name
    assert verdicts["stale"]["failed"] == ["goss_top_off"]
    assert "score_gap" in verdicts["state_unchanged"]["failed"]
