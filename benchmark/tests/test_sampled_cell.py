"""The sampled cell (``cdn-window.retrain``): its kind rehearsed at a few
thousand requests through the same code as a chip run, its generator
against the example it restates, its three readers over hand-made and
recorded runs, its shape's roofline, and the chip's readings judged by
the limits the cell ships with."""

import json
import os
import sys

import numpy as np
import pytest

from benchmark import roofline, run as bench_run
from benchmark.judge import compare
from benchmark.tests import probe_sampled, rehearse_sampled

BENCH = bench_run.load_json("BENCHMARK.json")
CELL = next(c for c in BENCH["workloads"]
            if c["name"] == rehearse_sampled.CELL)
HERE = os.path.dirname(os.path.abspath(__file__))
LIMITS = rehearse_sampled.cell_workload()["check"]["limits"]


# --- the kind, rehearsed --------------------------------------------------

@pytest.fixture(scope="module")
def tiny_result():
    kind = bench_run.load_plugin("kinds", "train_steady_sampled")
    return kind.run(rehearse_sampled.tiny_context(seed=2**31 + 3,
                                                  seconds=0.3, trace=True))


def test_tiny_run_is_correct_by_the_cells_own_limits(tiny_result):
    res = tiny_result
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 5
    assert res["attempted"] % 5 == 0            # whole bagging periods
    assert res["end_to_end"]["train_trees_per_s"] > 0
    assert res["end_to_end"]["setup_s"] > 0
    # every limit of the cell was read, the sampling's terms among them
    assert set(res["compared"]) == set(LIMITS)
    assert all(c["value"] is not None for c in res["compared"].values())
    assert res["readings"]["trees_checked"] == 5
    assert res["readings"]["bag_periods"] == 2
    assert res["notes"]["window_rows"]["rows"] == 6000


def test_result_line_reports_every_metric_the_cell_has(tiny_result):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = bench_run.result_line(BENCH, CELL, tiny_result, dev, trace=False)
    assert set(line["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    json.dumps(line)
    # a traced line on the CPU: the counters' metrics are there, the
    # device's (no device plane in a CPU trace) are left out, never 0
    traced = dict(tiny_result)
    traced["run"] = {**tiny_result["run"], "trace": {
        "busy_s": None, "window_s": 1.0, "device_ops": [],
        "idle_gaps": []}}
    line = bench_run.result_line(BENCH, CELL, traced, dev, trace=True)
    got = set(line["metrics"])
    assert {"bag_rows_pct", "bin_csr_mnnz_per_s", "bin_find_s",
            "bin_apply_s", "waves_per_tree", "hist_slot_use_pct",
            "hist_pad_row_pct", "window_compiles"} <= got
    assert "bag_draw_pct" not in got and "device_idle_pct" not in got
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert abs(line["metrics"]["bag_rows_pct"]["value"] - 80.0) < 2.0


def test_a_program_without_the_accessor_ends_the_run_at_once(monkeypatch):
    import lightgbm_tpu as lgb
    monkeypatch.delattr(lgb.Booster, "sampled_rows")
    kind = bench_run.load_plugin("kinds", "train_steady_sampled")
    ctx = rehearse_sampled.tiny_context()
    ctx.load = lambda *a: pytest.fail("nothing may be loaded or generated")
    with pytest.raises(SystemExit) as stop:
        kind.run(ctx)
    assert stop.value.code not in (0, None)


def test_the_cell_and_its_files_are_found_by_name():
    assert CELL["config"] == "cdn-window" and CELL["chips"] == 1
    cfg = bench_run.config_file(BENCH, CELL["config"])
    assert (cfg["rows"], cfg["features"]) == (20_000_000, 53)
    assert cfg["reduced"] == ["num_trees"]
    assert cfg["feature_mask_count"] == 43 == int(np.ceil(0.8 * 53))
    for key, value in {"objective": "binary", "num_leaves": 31,
                       "max_bin": 255, "learning_rate": 0.1,
                       "feature_fraction": 0.8, "bagging_fraction": 0.8,
                       "bagging_freq": 5, "min_data_in_leaf": 50,
                       "min_sum_hessian_in_leaf": 5.0}.items():
        assert cfg["params"][key] == value == cfg["published"][key]
    for folder, name in (("kinds", "train_steady_sampled"),
                         ("generators", cfg["generator"]),
                         ("references", cfg["reference"])):
        bench_run.load_plugin(folder, name)
    mine = [m for m in BENCH["per_layer"] if "workloads" in m]
    assert [m["name"] for m in mine] == ["bag_rows_pct", "bag_draw_pct",
                                         "bin_csr_mnnz_per_s"]
    assert all(m["workloads"] == [CELL["name"]] for m in mine)


# --- the generator ---------------------------------------------------------

def test_same_seed_same_window_and_the_examples_own_rows():
    gen = bench_run.load_plugin("generators", "cdn_gaps")
    cfg = {"rows": 5000, "features": 53,
           "trace": {"objects": 50, "cache_bytes": 2.0e4}}
    (x1, y1), (x2, y2) = gen.make(2**31 + 9, cfg), gen.make(2**31 + 9, cfg)
    x3, _ = gen.make(2**31 + 10, cfg)
    assert (x1 != x2).nnz == 0 and (y1 == y2).all()
    assert (x1 != x3).nnz > 0
    assert x1.shape == (5000, 53) and set(y1.tolist()) <= {0.0, 1.0}
    assert x1.data.dtype == np.float64 and 0.2 < y1.mean() < 0.5
    # the fork's derivation as examples/cache_admission.py restates it
    # (its loop over requests takes minutes at 20M; here it is the check)
    sys.path.insert(0, bench_run.ROOT)
    from examples import cache_admission as example
    ids, size, cost = gen.trace(2**31 + 9, 5000, 50)
    ids = ids.astype(np.int64)
    admit, _ = example.calculate_opt(ids, size[ids], 2.0e4, 5000)
    assert (admit == (y1 > 0)).all()
    _, indptr, indices, data = example.derive_features(
        ids, size[ids], cost[ids], admit, 2.0e4, 5000, 0, None)
    assert (indptr == x1.indptr).all() and (indices == x1.indices).all()
    assert (data == x1.data).all()
    # the sparsity is the trace's: min(k, 50) gaps and three more a row
    assert np.diff(x1.indptr).max() == 53 and np.diff(x1.indptr).min() == 3


# --- the readers -----------------------------------------------------------

WINDOW = {"span_n.train.chunk": 2, "grow.trees": 10, "grow.waves": 60,
          "grow.wave_slots": 1020, "grow.rows_scanned": 60 * 20_021_248,
          "grow.rows_real": 60 * 20_000_000,
          "grow.rows_in_bag": 2 * 5 * 16_000_900,
          "grow.features_in_mask": 430}
SETUP = {"span_n.dataset.construct": 1, "span_s.bin.apply": 12.5,
         "bin.csr_nnz": 508_235_944}
SCOPES = {"busy_s": 25.0, "lgb.wave_hist": {"self_s": 22.0},
          "lgb.bag_draw": {"self_s": 0.05}, "unscoped": {"self_s": 1.0}}
CASES = {"bag_rows_pct": 100.0 * 16_000_900 / 20_000_000,
         "bag_draw_pct": 0.2,
         "bin_csr_mnnz_per_s": 508.235944 / 12.5}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_value_and_silence(name):
    read = bench_run.load_plugin("layer_metrics", name).read
    run = {"setup_counters": SETUP, "window_counters": WINDOW,
           "scopes": SCOPES}
    assert read(run) == pytest.approx(CASES[name], rel=1e-12)
    # the parent commit's program: no such counters, no scopes in the run
    bare = {"setup_counters": {"span_n.dataset.construct": 1,
                               "span_s.bin.apply": 98.0},
            "window_counters": {"grow.trees": 5, "grow.waves": 45,
                                "grow.rows_real": 45 * 13_281_250},
            "trace": {"busy_s": 22.8}}
    assert read(bare) is None
    # a trace that never reaches the scope (a cell that does not bag)
    assert read({**bare, "scopes": {"busy_s": 22.8}}) is None


def test_roofline_of_the_cells_shape():
    least = roofline.least_seconds(20_000_000, 53, 31, 10,
                                   roofline.peaks_for("TPU v5 lite"))
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(10 * 6_900_000_000 / 819e9)
    assert least["ops_s"] == pytest.approx(10 * 31_800_000_000 / 197e12)


# --- the chip's readings ---------------------------------------------------

def _records():
    path = os.path.join(HERE, "data", "probe_chip_sampled.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


RECORDS = _records()
FAULTS = {"bag_ignored": "leaf_count_off", "stale_bag": "leaf_count_off",
          "mask_ignored": "mask_violations", "oob_not_updated": "score_gap",
          "state_unchanged": "score_gap", "int8_control": "gain_gap_rms",
          "fp8_control": "gain_gap_rms", "half_bag": "split_regret"}


def test_there_are_readings_of_the_cells_own_size():
    assert len(RECORDS) >= 8
    assert len({r["seed"] for r in RECORDS}) == len(RECORDS)
    for rec in RECORDS:
        assert rec["readings"]["bag_rows"] > 15_900_000
        assert rec["memory_peak_bytes"] >= 4 * 2**30
    # every stand-in was read on six seeds or more (the half bag, which
    # came with the last call, on three)
    for name in FAULTS:
        assert sum(name in probe_sampled.judge_stand_ins(
            r["readings"], LIMITS) for r in RECORDS) >= (
                3 if name == "half_bag" else 6), name


@pytest.mark.parametrize("rec", RECORDS, ids=lambda r: str(r["seed"]))
def test_sound_run_is_correct_and_every_stand_in_is_not(rec):
    judged = compare(rec["readings"], LIMITS)
    assert all(c["ok"] for c in judged.values()), judged
    verdicts = probe_sampled.judge_stand_ins(rec["readings"], LIMITS)
    for name, verdict in verdicts.items():
        assert not verdict["correct"], name
        assert FAULTS[name] in verdict["failed"], (name, verdict)
    # int8 rounded stochastically is not a control here: it reads under
    # the program's own bfloat16 (PERF.md section 4)
    sr = rec["readings"].get("int8_sr_control_gain_gap_rms")
    assert sr is None or sr < rec["readings"]["gain_gap_rms"]
