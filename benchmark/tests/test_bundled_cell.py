"""The bundled cell (``allstate-onehot.train``): its kind rehearsed at a
few thousand rows through the same code as a chip run, its four readers
over that run and over hand-made ones, its shape's roofline, and the
chip's readings judged by the limits the cell ships with."""

import json
import os

import pytest

from benchmark import roofline, roofline_find_best, run as bench_run
from benchmark.judge import compare
from benchmark.tests import probe_bundled, rehearse_bundled

BENCH = bench_run.load_json("BENCHMARK.json")
CELL = next(c for c in BENCH["workloads"]
            if c["name"] == rehearse_bundled.CELL)
HERE = os.path.dirname(os.path.abspath(__file__))
LIMITS = rehearse_bundled.cell_workload()["check"]["limits"]
NEW = ("bin_bundle_s", "efb_slot_fill_pct", "find_best_pct",
       "find_best_roofline")


def reader(name):
    return bench_run.load_plugin("layer_metrics", name).read


# --- the kind, rehearsed --------------------------------------------------

@pytest.fixture(scope="module")
def tiny_result():
    kind = bench_run.load_plugin("kinds", "train_steady_bundled")
    return kind.run(rehearse_bundled.tiny_context(seed=2**31 + 3,
                                                  seconds=0.3, trace=True))


def test_tiny_run_is_correct_by_the_cells_own_limits(tiny_result):
    res = tiny_result
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 5
    assert set(LIMITS) == set(res["compared"])
    assert res["readings"]["bundle_groups"] == len(res["notes"]["groups"])
    assert res["readings"]["bundle_groups"] < 60 < sum(res["notes"]["groups"])
    run = res["run"]
    assert run["shapes"]["features"] == 33       # the entries a row records
    assert run["gauges"]["bin.groups"] == res["readings"]["bundle_groups"]
    assert run["window_counters"]["grow.find_slots"] > 0


def test_result_line_holds_the_cells_metrics(tiny_result):
    cell = {"name": CELL["name"], "chips": 1}
    line = bench_run.result_line(BENCH, cell, tiny_result,
                                 {"platform": "cpu", "kind": "cpu",
                                  "count": 1}, trace=True)
    # the CPU's trace has no device plane: what reads one is left out
    assert {"bin_bundle_s", "efb_slot_fill_pct"} <= set(line["metrics"])
    assert 0 < line["metrics"]["efb_slot_fill_pct"]["value"] <= 100
    line = bench_run.result_line(BENCH, cell, tiny_result,
                                 {"platform": "cpu", "kind": "cpu",
                                  "count": 1}, trace=False)
    assert set(line["metrics"]) == {"train_trees_per_s", "setup_s"}


# --- the readers ------------------------------------------------------------

def test_the_cell_and_its_files_are_found_by_name():
    assert CELL["chips"] == 1 and CELL["config"] == "allstate-onehot"
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL["name"]]
            assert callable(reader(m["name"]))
    cfg = rehearse_bundled.cell_config()
    assert cfg["rows"] == 12_184_290 and cfg["features"] == 4228
    assert cfg["reduced"] == ["num_trees"]
    assert cfg["params"]["min_sum_hessian_in_leaf"] == 100


def test_readers_return_nothing_on_a_program_without_their_sources():
    """The parent's run: no gauges, no ``grow.find_slots``, no scopes."""
    run = {"setup_counters": {"span_s.bin.find": 3.0},
           "window_counters": {"grow.trees": 10}, "scopes": None,
           "device_kind": "TPU v5 lite"}
    for name in NEW:
        assert reader(name)(run) is None, name
    run["scopes"] = {"busy_s": 20.0, "lgb.wave_hist": {"self_s": 15.0}}
    assert reader("find_best_pct")(run) is None
    assert reader("find_best_roofline")(run) is None


def test_readers_over_a_hand_made_run():
    run = {"setup_counters": {"span_s.bin.bundle": 1.25},
           "window_counters": {"grow.find_slots": 10 * 509 * 7300},
           "gauges": {"bin.groups": 47, "bin.slots_used": 7347},
           "scopes": {"busy_s": 20.0, "lgb.find_best": {"self_s": 0.1}},
           "device_kind": "TPU v5 lite"}
    assert reader("bin_bundle_s")(run) == 1.25
    assert reader("efb_slot_fill_pct")(run) == pytest.approx(
        100 * 7347 / (47 * 256))
    assert reader("find_best_pct")(run) == pytest.approx(0.5)
    least = 10 * 509 * 7300 * 12 / 819e9
    assert roofline_find_best.least_seconds(
        10 * 509 * 7300, roofline.peaks_for("TPU v5 lite")) \
        == pytest.approx(least)
    assert reader("find_best_roofline")(run) == pytest.approx(
        100 * least / 0.1)


def test_the_cells_roofline_counts_the_entries_a_row_records():
    # 8 passes x 12,184,290 rows x (33 + 16) B = 4.78 GB = 5.8 ms a tree
    assert roofline.tree_bytes(12_184_290, 33, 255) == 4_776_241_680
    least = roofline.least_seconds(12_184_290, 33, 255, 1,
                                   roofline.peaks_for("TPU v5 lite"))
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(5.83e-3, rel=1e-2)


# --- the chip's readings, by the cell's own limits --------------------------

PROBE = os.path.join(HERE, "data", "probe_chip_bundled.jsonl")
with open(PROBE) as f:
    RECORDS = [json.loads(line) for line in f if line.strip()]
# (the first four probed seeds read the offset fault before the reference
# counted a split that parts nothing as gaining nothing: NaN, left out)
PROBED = [r for r in RECORDS
          if r["readings"].get("offset_fault_split_regret", float("nan"))
          == r["readings"].get("offset_fault_split_regret")]


def test_there_are_readings_of_the_cells_own_size():
    assert len(RECORDS) >= 8
    assert len({r["seed"] for r in RECORDS}) == len(RECORDS)
    assert len(PROBED) >= 3
    for rec in RECORDS:
        assert rec["notes"]["table"]["rows"] == 12_184_290
        assert rec["notes"]["table"]["entries_per_row"] == 33.0


@pytest.mark.parametrize("rec", RECORDS, ids=lambda r: str(r["seed"]))
def test_sound_run_is_correct(rec):
    judged = compare(rec["readings"], LIMITS)
    assert all(c["ok"] for c in judged.values()), judged


@pytest.mark.parametrize("rec", PROBED, ids=lambda r: str(r["seed"]))
def test_every_stand_in_with_rows_to_bite_on_is_not_correct(rec):
    verdicts = probe_bundled.judge_stand_ins(rec["readings"], LIMITS)
    assert set(verdicts) == set(probe_bundled.STAND_INS)
    for name, verdict in verdicts.items():
        if name == "earlier_kept" and \
                rec["readings"]["bundle_conflict_ppm"] == 0:
            # no row of this table records two columns of a bundle: the
            # fault has nothing to misread (tests/test_allstate_bundled.py
            # plants such rows and sees it fail)
            assert verdict["correct"]
            continue
        assert not verdict["correct"], name
    assert "gain_gap_rms" in verdicts["int8_control"]["failed"] \
        or "leaf_value_gap" in verdicts["int8_control"]["failed"]
    assert verdicts["offset_fault"]["failed"] == ["split_regret"]
    assert verdicts["default_zero"]["failed"] == ["split_regret"]
    assert "score_gap" in verdicts["state_unchanged"]["failed"]
